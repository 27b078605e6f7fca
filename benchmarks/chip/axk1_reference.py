"""The plain reference of ``A.X-K1`` (SK Telecom, ``model_type`` ``axk1``,
family "A.X K1 519B"), as one chip of its deployment computes it, with
its seeded weights and its checks.

``logits`` is the forward pass as the published ``config.json`` gives it
(ISSUE 40 has the equations), in straightforward ``jax.numpy`` and
float32 (callers set ``jax.default_matmul_precision("highest")``), with
no kernel, cache or sort, and shares nothing with ``horovod_tpu/models``
but the names of the parameter tree it is handed (``x`` a block's
normalised input, RMSNorm eps ``rms_norm_eps``):

    h = E[token]
    layer l:  h = h + Attention_l(RMSNorm(h))
              h = h + FFN_l(RMSNorm(h))
    logits = W_head RMSNorm(h)            (untied head)

``Attention_l``, multi-head latent attention **in its published,
non-absorbed form**, h over ``num_attention_heads``:
``c_q = RMSNorm(W_qa x)`` (``q_lora_rank``), ``[q_nope_h | q_pe_h] =
(W_qb c_q)_h`` (``qk_nope_head_dim``, ``qk_rope_head_dim``); ``[c_kv |
k_pe] = W_kva x``, ``c_kv <- RMSNorm(c_kv)`` (``kv_lora_rank``; ``k_pe``
one rotary key every head shares); ``[k_nope_h | v_h] = (W_kvb c_kv)_h``
(values ``v_head_dim``); scores ``(q_nope_h . k_nope_hj + rope(q_pe_h) .
rope(k_pe_j)) tau``, key ``j`` visible at ``i`` when ``j <= i``, a full
masked softmax; ``o = W_o concat_h(sum_j p_hj v_hj)``.  YaRN
(``rope_scaling``): the inverse frequency of rotary pair ``i`` is
``base^(-2i / R)`` below ``low``, that over ``factor`` above ``high``, a
linear ramp between, ``low`` and ``high`` the pairs that turn
``beta_fast`` and ``beta_slow`` times over
``original_max_position_embeddings`` (10 and 23 of 32); cos and sin are
scaled by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``
(1); ``tau = (nope + rope)^-1/2 m^2``, ``m = 0.1 mscale_all_dim
ln(factor) + 1`` (0.130861).  The expanded keys and values, a group of
heads and a block of queries at a time, only so that 14,336 positions
fit.

``FFN_l`` where ``l`` is in ``dense_layers`` (``first_k_dense_replace``):
``W_down (silu(W_gate x) * W_up x)`` at ``intermediate_size``.
Elsewhere: ``s = sigmoid(W_r x)`` over all ``router_experts``; the
experts lie in ``n_group`` groups of consecutive indices, a group scores
by the sum of its two largest ``s`` and the ``topk_group`` best are kept
(``topk_method`` ``none``: no correction bias), the
``num_experts_per_tok`` largest ``s`` among the kept groups' experts,
weights ``s_e / sum of the chosen`` (``norm_topk_prob``) times
``routed_scaling_factor``; ``y = shared(x) + sum_e w_e E_e(x)`` at
``moe_intermediate_size``.  **The chip's share**: the sum runs over the
chosen experts among ``experts_held`` (first, count), as a loop over
those experts, each computed for every token and masked; the shared
expert is whole; what the other chips' experts would add is left out,
here as in the program (the guide's section 4).  ``experts_share``
computes any one share, for the test that adds all of them up.

Departures from the published description: the weights are seeded
random ones (``weights``), since nothing can be downloaded here; the
checkpoint's rotary pairs are interleaved (``(2i, 2i + 1)``), a fixed
permutation of ``W_qb``'s and ``W_kva``'s rotary columns, and here they
are ``(i, i + R / 2)``, the program's pairing, which changes no number of
a model of seeded weights.  ``linear``, ``gated_mlp`` and the int8
rounding are ``solar_open2_reference.py``'s and ``reference.py``'s,
imported; ``sample_positions`` is ``mimo_v2_reference.py``'s.

``weights`` makes the tree the replica is handed: bfloat16, a normal law
of variance one over the fan-in for every linear map, the embedding and
the router; the scales of the two latent norms (``q_norm``,
``kv_norm``) uniform on 0.5 to 1.5, so that a program that leaves a norm
out fails (a latent of unit variance is its own RMSNorm to 6%); the
other norms ones.

``served_gap`` is the comparison every run of the cell makes, by the MiMo
cell's method: each sampled stream is **replayed through the program**
(``replay``: the prompt prefilled as the replica does it, every served
token fed to the family's decode step in a cache of one slot, which is
the absorbed form through ``hvd.mla_decode`` on the chip) and the
reference **follows the program's routing where its own scores tie
within ``served_check.tie``**, at the group's and at the expert's level.
It reads

- ``gap_mean``, ``gap``, ``replay_miss_mean``, ``replay_err``: as
  ``mimo_v2_reference.py`` reads them;
- ``attend_gap``: the program's attention output (each head's weighted
  value, before ``W_o``) against the reference's **expanded** float32
  softmax over **what the program fed its own** (its queries, its latent
  before the norm, its rotary key, all before positions), at
  ``served_check.attend_samples`` positions of the stream, the norm of
  the difference over the norm, the worst layer: on the decode steps'
  positions it holds the absorbed kernel to the published form: YaRN,
  ``tau``, the latent's norm, the row the kernel writes;
- ``route_gap``: the weights the program gave its chosen experts against
  ``s_e / sum x routed_scaling_factor`` over the program's own scores,
  the widest difference, and 1 where a choice lies outside the
  ``topk_group`` best groups of its own scores or is not the largest
  within them: the router's rule alone.

Its control is the reference with both operands of every linear map
rounded to 8 bits (``reference.int8``).  ``check`` is ``--check
reference``.
"""
from __future__ import annotations

import math

import numpy as np

import reference
from mimo_v2_reference import sample_positions
from solar_open2_reference import gated_mlp, linear

TOLERANCE = 0.025
ATTEND_BLOCK = 512       # query positions the reference holds at a time
HEAD_BLOCK = 8           # heads whose keys and values it expands at a time


# ------------------------------------------------------------- the equations
def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inverse_frequencies(cfg: dict) -> np.ndarray:
    """The rotary pairs' inverse frequencies under YaRN (plain where
    ``rope_scaling`` is none), float64."""
    width, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    plain = base ** -(np.arange(0, width, 2) / width)
    yarn = cfg["rope_scaling"]
    if not yarn:
        return plain
    original = yarn["original_max_position_embeddings"]

    def turns(beta):       # the pair that turns ``beta`` times over it
        return width * math.log(original / (beta * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(turns(yarn["beta_fast"])), 0)
    high = min(math.ceil(turns(yarn["beta_slow"])), width - 1)
    ramp = np.clip((np.arange(width // 2) - low) / max(high - low, 0.001),
                   0, 1)
    return plain * (1 - ramp) + plain / yarn["factor"] * ramp


def softmax_scale(cfg: dict) -> float:
    tau = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    yarn = cfg["rope_scaling"]
    if yarn and yarn.get("mscale_all_dim"):
        tau *= _mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    return tau


def rotary(x, positions, cfg: dict):
    """Rotary positions on ``x`` [..., T, H, R] at ``positions`` [T]:
    pairs ``(i, i + R / 2)``."""
    import jax.numpy as jnp
    yarn = cfg["rope_scaling"] or {}
    size = _mscale(yarn["factor"], yarn.get("mscale", 1)) \
        / _mscale(yarn["factor"], yarn.get("mscale_all_dim", 0)) \
        if yarn else 1.0
    half = x.shape[-1] // 2
    angle = positions[:, None].astype(jnp.float32) \
        * jnp.asarray(inverse_frequencies(cfg), jnp.float32)
    cos, sin = (size * f(angle)[:, None, :] for f in (jnp.cos, jnp.sin))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def latent_softmax(q_nope, q_pe, c_kv, k_pe, attn, cfg: dict, q_at=None,
                   operands=None):
    """The full masked softmax over expanded keys and values: ``q_nope``
    [B, Tq, H, N] and ``q_pe`` [B, Tq, H, R] at positions ``q_at`` [Tq]
    (every position where None) over the latent ``c_kv`` [B, T, C]
    **before its norm** and ``k_pe`` [B, T, R] at 0 to T - 1, all before
    their rotary positions; ``attn`` the layer's weights (``kv_norm``,
    ``wkv_b`` [C, H, N + V]) -> [B, Tq, H, V]."""
    import jax
    import jax.numpy as jnp
    b, t = c_kv.shape[:2]
    tq, heads, nope = q_nope.shape[1], q_nope.shape[2], \
        cfg["qk_nope_head_dim"]
    size = math.gcd(HEAD_BLOCK, heads)
    q_at = jnp.arange(t) if q_at is None else q_at
    c = reference.rms_norm(c_kv, attn["kv_norm"]["scale"],
                           cfg["rms_norm_eps"])
    k_pe = rotary(k_pe[:, :, None, :], jnp.arange(t), cfg)[:, :, 0]
    q_pe = rotary(q_pe, q_at, cfg)
    tau = softmax_scale(cfg)
    block = min(ATTEND_BLOCK, q_nope.shape[1])
    pad = -q_nope.shape[1] % block
    q_nope, q_pe = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    for x in (q_nope, q_pe))
    q_at = jnp.pad(q_at, (0, pad))
    blocks, groups = q_nope.shape[1] // block, heads // size

    def by_block(x):               # [B, Tq, H, D] -> [G, blocks, B, blk, g, D]
        return jnp.transpose(x.reshape(b, blocks, block, groups, size,
                                       x.shape[-1]), (3, 1, 0, 2, 4, 5))

    def a_group(args):
        qn, qp, w = args           # [blocks, B, blk, g, .], w [C, g, N + V]
        k_nope = linear("btc,cgn->btgn", c, -1, w[..., :nope], 0, operands)
        v = linear("btc,cgv->btgv", c, -1, w[..., nope:], 0, operands)

        def one(each):
            qn_, qp_, pos = each
            scores = tau * (jnp.einsum("bqgn,bsgn->bgqs", qn_, k_nope)
                            + jnp.einsum("bqgr,bsr->bgqs", qp_, k_pe))
            seen = jnp.arange(t)[None, :] <= pos[:, None]
            weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("bgqs,bsgv->bqgv", weights, v)
        return jax.lax.map(one, (qn, qp, q_at.reshape(blocks, block)))

    wkv_b = attn["wkv_b"].reshape(attn["wkv_b"].shape[0], groups, size, -1)
    mixed = jax.lax.map(a_group, (by_block(q_nope), by_block(q_pe),
                                  jnp.moveaxis(wkv_b, 1, 0)))
    mixed = jnp.transpose(mixed, (2, 1, 3, 0, 4, 5))  # [B, blocks, blk, G, g]
    return mixed.reshape(b, blocks * block, heads, -1)[:, :tq]


def projections(layer, x, cfg: dict, operands=None):
    """What a latent layer's softmax is fed: ``(q_nope, q_pe, c_kv,
    k_pe)`` from the block's input, ``c_kv`` before its norm."""
    h = reference.rms_norm(x, layer["mixer_norm"]["scale"],
                           cfg["rms_norm_eps"])
    attn = layer["attn"]
    c_q = reference.rms_norm(
        linear("btd,dr->btr", h, -1, attn["wq_a"]["kernel"], 0, operands),
        attn["q_norm"]["scale"], cfg["rms_norm_eps"])
    q = linear("btr,rhk->bthk", c_q, -1, attn["wq_b"]["kernel"], 0, operands)
    kv = linear("btd,dc->btc", h, -1, attn["wkv_a"]["kernel"], 0, operands)
    nope, rank = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    return q[..., :nope], q[..., nope:], kv[..., :rank], kv[..., rank:]


def attention(layer, x, cfg: dict, operands=None):
    mixed = latent_softmax(*projections(layer, x, cfg, operands),
                           layer["attn"], cfg, operands=operands)
    return x + linear("bthv,hvd->btd", mixed, (-2, -1),
                      layer["attn"]["wo"]["kernel"], (0, 1), operands)


def best_groups(scores, cfg: dict, bonus=None):
    """[N, E] -> [N, n_group]: whether each group is one of the token's
    ``topk_group`` best (a group's score: the sum of its two largest,
    plus ``bonus`` [N, n_group] where given)."""
    import jax
    import jax.numpy as jnp
    n, e = scores.shape
    groups = cfg["n_group"]
    score = jnp.sum(jax.lax.top_k(scores.reshape(n, groups, e // groups),
                                  2)[0], -1)
    if bonus is not None:
        score = score + bonus
    best = jax.lax.top_k(score, cfg["topk_group"])[1]
    return jnp.zeros((n, groups), bool).at[jnp.arange(n)[:, None],
                                           best].set(True)


def group_kept(scores, cfg: dict, bonus=None):
    """[N, E] -> whether each expert lies in one of the token's best
    groups (``best_groups``)."""
    import jax.numpy as jnp
    return jnp.repeat(best_groups(scores, cfg, bonus),
                      scores.shape[-1] // cfg["n_group"], axis=-1)


def routing(scores, cfg: dict, follow=None, tie=0.0):
    """``scores`` [N, E] -> ``(weights [N, k], chosen [N, k], flipped
    [N], margin [N])``: the ``num_experts_per_tok`` largest of the
    experts of the best groups.  With ``follow = (chosen [N, k], scores
    [N, E])``, the experts the program took and its own scores, each of
    those experts counts ``tie`` more in the choice, and each group that
    the program's scores keep ``tie`` more in the groups' (a group may
    be kept and hold none of the chosen), not in a weight.
    ``flipped``: the choice is not the scores' own; ``margin``: how far
    the lowest score of ``follow`` lies below the scores' own cut."""
    import jax
    import jax.numpy as jnp
    n, e = scores.shape
    k, rows = cfg["num_experts_per_tok"], jnp.arange(n)[:, None]
    own_top, own = jax.lax.top_k(
        jnp.where(group_kept(scores, cfg), scores, -jnp.inf), k)
    chosen, flipped = own, jnp.zeros(n, bool)
    margin = jnp.zeros(n, scores.dtype)
    if follow is not None:
        follow, theirs = follow
        taken = jnp.zeros(scores.shape, bool).at[rows, follow].set(True)
        bonus = tie * best_groups(theirs, cfg)
        _, chosen = jax.lax.top_k(jnp.where(
            group_kept(scores, cfg, bonus), scores + tie * taken, -jnp.inf),
            k)
        mine = jnp.zeros(scores.shape, bool).at[rows, own].set(True)
        picked = jnp.zeros(scores.shape, bool).at[rows, chosen].set(True)
        flipped = jnp.any(picked != mine, -1)
        margin = jnp.maximum(own_top[:, -1] - jnp.min(
            jnp.take_along_axis(scores, follow, -1), -1), 0.0)
    return router_weights(scores, chosen, cfg), chosen, flipped, margin


def router_weights(scores, chosen, cfg: dict):
    """``s_e`` over the sum of the ``chosen`` (``norm_topk_prob``) times
    ``routed_scaling_factor``."""
    import jax.numpy as jnp
    top = jnp.take_along_axis(scores, chosen, -1)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return top * cfg["routed_scaling_factor"]


def experts_share(w, x, cfg: dict, held, operands=None, follow=None,
                  tie=0.0, seen=None):
    """``x`` [N, d], normalised -> what the chip holding the experts
    ``held = (first, count)`` adds for them: ``sum_e w_e E_e(x)`` over
    the chosen experts among its own (no shared expert).  ``w`` has that
    chip's expert weights, ``[count, ...]``, and the whole router.
    ``follow``, ``tie``: see ``routing``, whose ``flipped`` and
    ``margin`` a dict given as ``seen`` receives."""
    import jax
    import jax.numpy as jnp
    first, count = held
    scores = jax.nn.sigmoid(linear(
        "nd,de->ne", x, -1, w["router"].astype(jnp.float32), 0, operands))
    top, chosen, flipped, margin = routing(scores, cfg, follow, tie)
    if seen is not None:
        seen.update(flipped=flipped, margin=margin)

    def one(y, expert):
        at, gate, up, down = expert
        weight = jnp.sum(jnp.where(chosen == first + at, top, 0.0), -1)
        full = (each.astype(jnp.float32) for each in (gate, up, down))
        return y + weight[:, None] * gated_mlp(x, *full, operands), ()

    return jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(count), w["experts_gate"], w["experts_up"],
        w["experts_down"]))[0]


def feed_forward(layer, x, cfg: dict, operands=None, **routed):
    """The dense MLP where the layer has one, else the shared expert and
    this chip's share of the routed experts."""
    h = reference.rms_norm(x, layer["mlp_norm"]["scale"].astype("float32"),
                           cfg["rms_norm_eps"])
    flat = h.reshape(-1, h.shape[-1])
    if "mlp" in layer:
        out = gated_mlp(flat, *(layer["mlp"][name]["kernel"].astype("float32")
                                for name in ("gate", "up", "down")), operands)
        if routed.get("seen") is not None:
            routed["seen"].update(flipped=0, margin=0.0)
    else:
        w = layer["moe"]
        out = gated_mlp(flat, *(w[name]["kernel"].astype("float32")
                                for name in ("shared_gate", "shared_up",
                                             "shared_down")), operands) \
            + experts_share(w, flat, cfg, cfg["experts_held"], operands,
                            **routed)
    return x + out.reshape(x.shape)


def embed(params, tokens, cfg):
    return params["embed"]["embedding"][tokens]


def head(params, x, cfg, operands=None):
    x = reference.rms_norm(x, params["final_norm"]["scale"],
                           cfg["rms_norm_eps"])
    return linear("btd,dv->btv", x, -1, params["lm_head"]["kernel"], 0,
                  operands)


def logits(params, tokens, cfg: dict, operands=None):
    """tokens [B, T] -> logits [B, T, vocab], float32."""
    x = embed(params, tokens, cfg)
    for i in range(len(cfg["layer_types"])):
        layer = params[f"layer_{i}"]
        x = attention(layer, x, cfg, operands)
        x = feed_forward(layer, x, cfg, operands)
    return head(params, x, cfg, operands)


# ---------------------------------------------------------------- the weights
def weights(run, held=None):
    """The configuration's weights from the seed, made on the device;
    the tree has the names the program's hybrid decoder gives its
    parameters and nothing else of the program.  ``held`` (first, count)
    makes another chip's share of the experts (the tests); an expert's
    weights depend on its index in the model, not on who holds it."""
    import jax
    import jax.numpy as jnp

    cfg = run.config
    dtype = run.resolve(cfg["model"]["args"]["param_dtype"][1:])
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    heads, nope, rope = cfg["num_attention_heads"], \
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    q_rank, rank, wide = cfg["q_lora_rank"], cfg["kv_lora_rank"], \
        cfg["v_head_dim"]
    ff, shared_ff = cfg["moe_intermediate_size"], \
        cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    first, count = held or cfg["experts_held"]
    normal = lambda fan_in, *shape: ("normal", shape, fan_in)   # noqa: E731
    layer = {
        "mixer_norm": {"scale": ("ones", (d,), 0)},
        "mlp_norm": {"scale": ("ones", (d,), 0)},
        "attn": {"wq_a": {"kernel": normal(d, d, q_rank)},
                 "q_norm": {"scale": ("norm", (q_rank,), 0)},
                 "wq_b": {"kernel": normal(q_rank, q_rank, heads,
                                           nope + rope)},
                 "wkv_a": {"kernel": normal(d, d, rank + rope)},
                 "kv_norm": {"scale": ("norm", (rank,), 0)},
                 "wkv_b": normal(rank, rank, heads, nope + wide),
                 "wo": {"kernel": normal(heads * wide, heads, wide, d)}}}

    def ffn(dense):
        if dense:
            ffw = cfg["intermediate_size"]
            return {"mlp": {"gate": {"kernel": normal(d, d, ffw)},
                            "up": {"kernel": normal(d, d, ffw)},
                            "down": {"kernel": normal(ffw, ffw, d)}}}
        return {"moe": {"router": normal(d, d, cfg["router_experts"]),
                        "shared_gate": {"kernel": normal(d, d, shared_ff)},
                        "shared_up": {"kernel": normal(d, d, shared_ff)},
                        "shared_down": {"kernel": normal(shared_ff,
                                                         shared_ff, d)}}}

    expert = {"experts_gate": normal(d, d, ff), "experts_up": normal(d, d, ff),
              "experts_down": normal(ff, ff, d)}
    outer = {"embed": {"embedding": normal(d, vocab, d)},
             "final_norm": {"scale": ("ones", (d,), 0)},
             "lm_head": {"kernel": normal(d, d, vocab)}}

    def draw(key, law, shape, fan_in):
        if law == "ones":
            return jnp.ones(shape, dtype)
        if law == "normal":
            return (fan_in ** -0.5 * jax.random.normal(
                key, shape, jnp.float32)).astype(dtype)
        return jax.random.uniform(key, shape, jnp.float32, 0.5,
                                  1.5).astype(dtype)

    def maker(tree):
        """One compiled program for a tree of laws, called with each
        layer's (or each expert's) key."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, tuple))
        return jax.jit(lambda key: jax.tree_util.tree_unflatten(treedef, [
            draw(jax.random.fold_in(key, at), *spec)
            for at, (_, spec) in enumerate(flat)]))

    key = jax.random.key(run.seed)
    makers = {dense: maker({**layer, **ffn(dense)}) for dense in (True,
                                                                  False)}
    an_expert = maker(expert)
    stack = jax.jit(lambda *each: jnp.stack(each))
    params = maker(outer)(jax.random.fold_in(key, 0))
    for i in range(len(cfg["layer_types"])):
        dense = i in cfg["dense_layers"]
        layer_key = jax.random.fold_in(key, 1 + i)
        made = makers[dense](layer_key)
        if not dense:
            held_here = [an_expert(jax.random.fold_in(layer_key, 1000 + e))
                         for e in range(first, first + count)]
            made["moe"].update({name: stack(*(e[name] for e in held_here))
                                for name in expert})
        params[f"layer_{i}"] = made
    return params


# ------------------------------------------------- what every run compares
FED = ("q_nope", "q_pe", "c_kv", "k_pe")
KEYS = ("c_kv", "k_pe")                  # kept at every position
SAMPLED = ("q_nope", "q_pe", "out")      # kept at the sampled positions


def replay(cfg: dict):
    """The program, replayed on one stream it served: a function of
    ``(params, tokens [1, T], first, length)`` that prefills the prompt
    ``tokens[0, :first]`` as the replica does (a batch of one, padded to
    a bucket, here the widest, the true length passed) and feeds
    ``tokens[0, first:length - 1]`` to the family's decode step, one
    token at a time in a cache of one slot.  It returns the program's
    ``logits`` [T, vocab] (position t predicts token t + 1; rows outside
    ``first - 1 .. length - 2`` are zeros); of each expert layer the
    experts every token took, ``chosen`` [layers, T, k], its ``scores``
    [layers, T, E] and the ``weights`` [layers, T, k] it gave them; what
    every latent layer's softmax was fed at every position, ``c_kv``
    {layer: [T, C]} and ``k_pe`` {layer: [T, R]}; and at the ``at``
    [samples] positions of ``sample_positions`` its queries and what
    came out, ``q_nope``, ``q_pe`` and ``out`` {layer: [samples, H,
    D]}.  The model is built from the configuration's file as ``run.py``
    builds it."""
    import jax
    import jax.numpy as jnp
    import run as harness

    config = harness.resolve(cfg["model"]["config"])(**{
        **harness.build_args(cfg), "decode": True,
        "max_seq_len": cfg["serve"]["max_seq"]})
    family = config.family
    model = family.build(config)
    layers = range(len(cfg["layer_types"]))
    routed = [i for i in layers if i not in cfg["dense_layers"]]
    bucket = max(cfg["serve"]["warmup_buckets"])     # one shape for all
    samples = cfg["served_check"]["attend_samples"]
    ROUTING = ("chosen", "scores", "weights")

    def took(sown):                        # name -> [expert layers, N, ...]
        return {name: jnp.stack([
            sown["routing"][f"layer_{i}"]["moe"][name][0] for i in routed])
            for name in ROUTING}

    def fed_to(sown, name, i):             # [T', ...] of layer i
        return sown["attention"][f"layer_{i}"]["attn"][name][0][0]

    def run(params, tokens, first, length):
        variables = {"params": params}
        positions = tokens.shape[1]
        at, _ = sample_positions(first, length, samples)
        slot_of = jnp.full(positions, samples, jnp.int32) \
            .at[at].set(jnp.arange(samples))     # the last of a repeat
        prompt = jnp.where(jnp.arange(bucket) < first, tokens[:, :bucket], 0)
        sown = {"routing": {}, "attention": {}}
        logits_, cache = family.prefill(model, variables, prompt,
                                        lengths=first, sown=sown)
        rows = jnp.zeros((positions, logits_.shape[-1]), jnp.float32) \
            .at[first - 1].set(logits_[0, first - 1].astype(jnp.float32))

        def whole(value):          # [layers', bucket, ...] -> [.., T, ..]
            return jnp.zeros((value.shape[0], positions, *value.shape[2:]),
                             value.dtype).at[:, :bucket].set(value)

        def sampled(value):        # [bucket, ...] -> [samples + 1, ...]
            return jnp.zeros((samples + 1, *value.shape[1:]), value.dtype) \
                .at[slot_of[:bucket]].set(value)

        routing_ = {name: whole(value) for name, value in took(sown).items()}
        keys = {name: {i: whole(fed_to(sown, name, i)[None])[0]
                       for i in layers} for name in KEYS}
        some = {name: {i: sampled(fed_to(sown, name, i)) for i in layers}
                for name in SAMPLED}

        def step(pos, carry):
            cache, rows, routing_, keys, some = carry
            sown = {"routing": {}, "attention": {}}
            logits_, cache = family.decode_step(
                model, variables, cache,
                jax.lax.dynamic_slice_in_dim(tokens, pos, 1, axis=1),
                sown=sown)
            now = took(sown)
            return (cache,
                    rows.at[pos].set(logits_[0, 0].astype(jnp.float32)),
                    {name: routing_[name].at[:, pos].set(now[name][:, 0])
                     for name in ROUTING},
                    {name: {i: keys[name][i].at[pos].set(
                        fed_to(sown, name, i)[0]) for i in layers}
                     for name in KEYS},
                    {name: {i: some[name][i].at[slot_of[pos]].set(
                        fed_to(sown, name, i)[0]) for i in layers}
                     for name in SAMPLED})

        cache, rows, routing_, keys, some = jax.lax.fori_loop(
            first, length - 1, step, (cache, rows, routing_, keys, some))
        return {"logits": rows, **routing_, **keys,
                **{name: {i: value[:samples] for i, value in each.items()}
                   for name, each in some.items()}}

    jitted = jax.jit(run)

    def replayed(params, tokens, first, length):
        if first > bucket:
            raise ValueError(f"a prompt of {first} tokens in a bucket of "
                             f"{bucket}")
        return jitted(params, tokens, first, length)

    return replayed


def served_gap(cfg: dict, control: bool = False):
    """The comparison of one finished request with the reference, as a
    function of ``(params, tokens, first, length)`` with the arguments
    of ``reference.lm_served_gap``; the head of this file says what it
    returns (a number and, where it is a mean over the served tokens,
    its ``_sum``; with ``control`` each also as ``control_...``).  A
    layer at a time: the attention, the dense MLP and the expert block
    are jitted functions that are handed their layer's weights and cast
    them to float32 themselves (an expert inside the loop over the
    experts), so that one sublayer is on the device in float32 at
    once."""
    import jax
    import jax.numpy as jnp

    tie = cfg["served_check"]["tie"]
    samples = cfg["served_check"]["attend_samples"]
    replayed = replay(cfg)
    kinds = cfg["layer_types"]
    routed = [i for i in range(len(kinds)) if i not in cfg["dense_layers"]]

    def full(tree):
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)

    def mixer_fn(operands):
        def run_mixer(layer, x):
            with jax.default_matmul_precision("highest"):
                return attention(full(layer), x, cfg, operands)
        return jax.jit(run_mixer)

    def ffn_fn(operands):
        def run_ffn(layer, x, follow):     # the experts cast one by one
            seen = {}
            with jax.default_matmul_precision("highest"):
                x = feed_forward(layer, x, cfg, operands, follow=follow,
                                 tie=tie, seen=seen)
            return x, seen
        return jax.jit(run_ffn)

    def head_fn(operands):
        def run_head(outer, x):
            with jax.default_matmul_precision("highest"):
                return head(full(outer), x, cfg, operands)[0]
        return jax.jit(run_head)

    passes = {"": None, **({"control_": reference.int8} if control else {})}
    mixers = {name: mixer_fn(how) for name, how in passes.items()}
    blocks = {name: ffn_fn(how) for name, how in passes.items()}
    heads = {name: head_fn(how) for name, how in passes.items()}

    def forward(name, params, tokens, follow):
        """One pass of the reference -> (logits [T, vocab], tokens whose
        routing is not the scores' own a layer, the widest margin of a
        followed choice)."""
        outer = {key: params[key]
                 for key in ("embed", "final_norm", "lm_head")}
        x = embed(outer, tokens, cfg).astype(jnp.float32)
        flipped, margin = 0, 0.0
        for i in range(len(kinds)):
            layer = params[f"layer_{i}"]
            x = mixers[name](
                {key: layer[key] for key in ("mixer_norm", "attn")}, x)
            x, seen = blocks[name](
                {key: value for key, value in layer.items()
                 if key in ("mlp_norm", "mlp", "moe")}, x,
                tuple(each[routed.index(i)] for each in follow)
                if i in routed else None)
            flipped = flipped + seen["flipped"]
            margin = jnp.maximum(margin, seen["margin"])
        return heads[name](outer, x), flipped, margin

    @jax.jit
    def read(logits_, served, chosen, live):
        """(widest, sum) of the gaps of ``chosen`` below the best of
        ``logits_`` and the count of ``chosen`` that are not ``served``,
        over the live positions."""
        below = jnp.where(live, jnp.max(logits_, -1) - jnp.take_along_axis(
            logits_, chosen[:, None], -1)[:, 0], 0.0)
        return jnp.max(below), jnp.sum(below), \
            jnp.sum(live & (chosen != served))

    @jax.jit
    def off(got, want, live):
        """The root mean square over the vocabulary of the difference,
        over that of ``want``, the worst live position."""
        rms = lambda x: jnp.sqrt(jnp.mean(x * x, -1))        # noqa: E731
        return jnp.max(jnp.where(live, rms(got - want) / rms(want), 0.0))

    @jax.jit
    def attend_gap(q_nope, q_pe, c_kv, k_pe, out, attn, at, counts):
        """The program's attention output at the sampled positions
        against the expanded float32 softmax over what it was fed: the
        norm of the difference over the norm."""
        with jax.default_matmul_precision("highest"):
            want = latent_softmax(
                *(each.astype(jnp.float32)[None]
                  for each in (q_nope, q_pe, c_kv, k_pe)),
                full(attn), cfg, at)[0]
        keep = counts[:, None, None]
        return jnp.linalg.norm(jnp.where(keep, out - want, 0.0)) \
            / jnp.linalg.norm(jnp.where(keep, want, 0.0))

    @jax.jit
    def routed_gap(scores, chosen, weights_, live):
        """The widest difference of the program's weights from ``s_e /
        sum x routed_scaling_factor`` over its own scores; 1 where a
        choice lies outside the best groups of its own scores or is not
        the largest within them (the cut's ties apart)."""
        flat = scores.reshape(-1, scores.shape[-1])
        masked = jnp.where(group_kept(flat, cfg), flat,
                           -jnp.inf).reshape(scores.shape)
        cut = jax.lax.top_k(masked, chosen.shape[-1])[0][..., -1:]
        mine = jnp.take_along_axis(masked, chosen, -1)
        wrong = jnp.any(mine < cut, -1)
        want = router_weights(scores, chosen, cfg)
        apart = jnp.max(jnp.abs(weights_ - want), -1)
        return jnp.max(jnp.where(live[None], jnp.maximum(apart, wrong), 0.0))

    def gaps(params, tokens, first, length):
        tokens = jnp.asarray(tokens)
        at = jnp.arange(tokens.shape[1])
        live = (at >= first - 1) & (at < length - 1)   # t predicts t + 1
        served = jnp.roll(tokens[0], -1)
        program = replayed(params, tokens, first, length)
        put_first = jnp.argmax(program["logits"], -1)
        follow = (program["chosen"], program["scores"])
        logits_, flipped, margin = forward("", params, tokens, follow)
        _, gap_sum, _ = read(logits_, served, served, live)
        gap, _, missed = read(logits_, served, put_first, live)
        sampled, counts = sample_positions(first, length, samples)
        worst = max(float(attend_gap(
            *(program[name][i] for name in FED), program["out"][i],
            params[f"layer_{i}"]["attn"], sampled, counts))
            for i in range(len(kinds)))
        seen = {"gap": gap, "gap_sum": gap_sum, "replay_miss_sum": missed,
                "replay_err": off(program["logits"], logits_, live),
                "attend_gap": worst,
                "route_gap": routed_gap(program["scores"], program["chosen"],
                                        program["weights"], live),
                "route_margin": jnp.max(jnp.where(live, margin, 0.0)),
                "route_flips_sum": jnp.sum(jnp.where(live, flipped, 0))}
        if control:
            lower, _, _ = forward("control_", params, tokens, follow)
            gap, gap_sum, missed = read(logits_, served,
                                        jnp.argmax(lower, -1), live)
            seen.update({"control_gap": gap, "control_gap_sum": gap_sum,
                         "control_replay_miss_sum": missed,
                         "control_replay_err": off(lower, logits_, live),
                         # The control is of the reference's linear maps:
                         # what the program fed its own is not its to
                         # round.
                         "control_attend_gap": worst,
                         "control_route_gap": seen["route_gap"]})
        return seen

    return gaps


# -------------------------------------------------------- --check reference
def check(run, cfg: dict) -> dict:
    """Prefill one prompt as the executor does (a batch of one, padded to
    its bucket, the true length passed), insert it into a slot of a slot
    cache, then decode through the cache (the absorbed form); every row
    against ``logits`` (the expanded form).  The dense layer and two
    expert layers."""
    import jax
    import jax.numpy as jnp

    kinds = ("latent",) * 3
    positions = 512 if cfg["hidden_size"] > 1024 else 64
    prompt, decoded = positions * 5 // 8 - 3, positions // 4
    slots, slot = 4, 3
    cfg = {**cfg, "layer_types": kinds, "dense_layers": [0]}
    config = run.model_config(layer_types=kinds, dense_layers=(0,),
                              decode=True, max_seq_len=positions)
    family = config.family
    model = family.build(config)
    tokens = jax.random.randint(jax.random.key(run.seed), (1, positions),
                                2, cfg["vocab_size"])
    params = jax.jit(model.init)(jax.random.key(run.seed),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    padded = tokens.at[:, prompt:].set(0)           # the bucket's padding
    logits_, cache1 = jax.jit(lambda p, t: family.prefill(
        model, {"params": p}, t, lengths=prompt))(params, padded)
    rows = [logits_[0, prompt - 1]]
    cache = jax.tree_util.tree_map(
        lambda big, small: big.at[slot].set(small[0]),
        jax.jit(lambda p: family.fresh_cache(model, p, slots))(params),
        cache1)
    decode = jax.jit(lambda p, c, t: family.decode_step(
        model, {"params": p}, c, t))
    for at in range(prompt, prompt + decoded):
        fed = jnp.zeros((slots, 1), jnp.int32).at[slot, 0].set(
            tokens[0, at])
        logits_, cache = decode(params, cache, fed)
        rows.append(logits_[slot, 0])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: logits(p, tokens[:, :prompt + decoded],
                                        cfg))(
            jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params))
    return {"compared": f"{len(rows)} logit rows (prefill of {prompt} "
                        f"tokens in a bucket of {positions} into slot "
                        f"{slot}, then {decoded} decoded through the "
                        f"cache) x {cfg['vocab_size']}",
            "layers": list(kinds), "positions": positions,
            "tolerance": TOLERANCE,
            "error": reference.error(jnp.stack(rows),
                                     want[0, prompt - 1:])}

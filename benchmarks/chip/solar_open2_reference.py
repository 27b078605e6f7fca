"""The plain reference of ``Solar-Open2-250B`` (Upstage Solar Open 2,
``model_type`` ``solar_open2``, "250B-A15B"), as one chip of its
deployment computes it, with its seeded weights and its checks.

``logits`` is the forward pass as the published ``config.json`` and the
papers it rests on give it, in straightforward ``jax.numpy`` and float32
(callers set ``jax.default_matmul_precision("highest")``), with no
kernel, cache, chunk or sort, and shares nothing with
``horovod_tpu/models`` but the names of the parameter tree it is handed
(``x`` a block's normalised input, RMSNorm eps ``rms_norm_eps``):

    h = E[token]                          (no positional term anywhere)
    layer l:  h = h + Mixer_l(RMSNorm(h))
              h = h + Experts(RMSNorm(h))
    logits = W_head RMSNorm(h)            (untied head)

``Mixer_l`` where ``layer_types[l] == "kda"`` (Kimi Delta Attention,
arXiv:2510.26692; H heads, keys and values of ``D`` channels):
``q, k, v = W_q x, W_k x, W_v x``, each through a causal depthwise
convolution of ``short_conv_kernel_size`` with SiLU; ``q`` and ``k``
divided by their norm a head, ``q`` times ``D^-0.5``;
``g_t = -exp(A_log[h]) softplus(W_f2 W_f1 x_t + dt_bias)`` a key
channel, ``a_t = exp(g_t)``; ``b_t = 2 sigmoid(W_b x_t)`` a head
(``kda_allow_neg_eigval``: the 2);

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

as a ``lax.scan`` over positions, one token at a time;
``W_o (RMSNorm_head(o_t) * sigmoid(W_g2 W_g1 x_t))``.  Where it is
``"attention"``: ``num_attention_heads`` query heads over
``num_key_value_heads`` key-value heads of ``head_dim``, scale
``head_dim^-0.5``, a full masked softmax (a key-value head at a time:
64 heads of 4,608 x 4,608 scores are 5.4 GB), ``W_o (attend(q, k, v) *
sigmoid(W_gate x))`` (``use_gqa_gate``, elementwise).

``Experts`` (every layer; ``first_k_dense_replace`` 0):
``s = sigmoid(W_r x)`` over all ``router_experts`` of the model, the
``num_experts_per_tok`` largest, weights ``s_e / sum of the chosen``
(``norm_topk_prob``) times ``routed_scaling_factor``; ``y = sum_e w_e
E_e(x) + E_shared(x)``, ``E(x) = W_down (silu(W_gate x) * W_up x)`` at
width ``moe_intermediate_size``.  **The chip's share**: the sum runs
over the chosen experts among ``experts_held`` (first, count), as a loop
over those experts, each computed for every token and masked; what the
other chips' experts would add is left out, here as in the program, and
the partial result goes on to the next layer (the guide's section 4).
``experts_share`` computes any one share, for the test that adds all of
them up.

Departures from the published description: the weights are seeded
random ones (``weights``), since nothing can be downloaded here; the
projections of ``x`` that a KDA layer makes lie side by side in one
matrix, ``in_proj`` (``q | k | v | W_f1 | W_g1 | W_b``), which changes
no number; a head's norm has ``1e-6`` under its root.  The sizes the
configuration's file lists under ``assumed`` are read from it.

``weights`` makes the tree the replica is handed: bfloat16, a normal law
of variance one over the fan-in for every linear map, the embedding and
the convolution; ``A_log = log(u)``, ``u`` uniform on 1 to 16, and
``dt_bias`` the inverse softplus of a log-uniform draw on 0.001 to 0.1
(float32 both), ones for the norms.

``served_gap`` is the comparison every run of the cell makes
(``serve.py`` says over which requests), a layer at a time, an expert at
a time, so that no more than one expert's weights and one layer's mixer
are on the device in float32 at once.  Tokens alone cannot tell what the
cell is named for: the state in bfloat16 moves a logit less than the
bfloat16 activations do, and a rounding that swaps a token's eighth and
ninth router scores moves it by tenths (``PERF.md``, PR 34).  So the
served stream is first **replayed through the program** (``replay``:
the prompt prefilled as the replica does it, then every served token fed
to the family's decode step in a cache of one slot), which shows what
the serving interface does not return: the program's logits at every
position, the experts each token took, and the slot's final delta-rule
state.  The reference then **follows the program's routing where its own
scores tie** (an expert the program took counts ``served_check.tie``
more: it is taken where the reference's own score for it lies within
``tie`` of the reference's cut, and nowhere else, so a program that
routes wrongly is not followed) and reads

- ``gap_mean``: the mean gap by which a served token's logit lies below
  the reference's best (``reference.lm_served_gap``'s meaning);
- ``gap``: the widest such gap of the token the replay puts first, which
  is the served one wherever the replay reproduces the stream;
- ``replay_miss_mean``: the share of served tokens it does not;
- ``state_gap``: the slot's final state against the state that the
  reference's recurrence (``recurrence``, float32, a position at a time)
  reaches **from what the program fed its own** (the keys, values,
  decays and write strengths of every position, which the replay
  collects), the norm of the difference over the norm, the worst KDA
  layer: the recurrence's arithmetic alone, apart from the bfloat16
  noise of what feeds it, which is five times what a state in bfloat16
  adds (``PERF.md``, PR 34);

- ``replay_err``: the program's logits against the reference's, the
  root mean square of the difference over the vocabulary over that of
  the reference's, the worst position: the whole forward pass, every
  logit of every position and not the best one alone;

and for the notes ``state_err`` (that final state against the whole
reference's own: the noise of what feeds the recurrence),
``route_margin`` (how far below the reference's cut the program's worst
choice lies, in the reference's scores) and ``route_flips_mean`` (layers
a token in which the routing followed is not the reference's own).  Its
control is the precision below the configuration's on both counts: both
operands of every linear map rounded to 8 bits (``reference.int8``) and
the state to bfloat16 after every position (in the whole pass, and in
the recurrence over what the program fed for ``control_state_gap``).
``check`` is ``--check reference``.
"""
from __future__ import annotations

import math

import reference

TOLERANCE = 0.025
KINDS = ("kda", "attention")
L2_EPS = 1e-6


# ------------------------------------------------------------- the equations
def linear(spec, x, x_axes, w, w_axes, operands=None):
    import jax.numpy as jnp
    if operands is not None:
        x, w = operands(x, x_axes), operands(w, w_axes)
    return jnp.einsum(spec, x, w)


def gated_mlp(x, gate, up, down, operands=None):
    import jax
    hidden = jax.nn.silu(linear("nd,df->nf", x, -1, gate, 0, operands)) \
        * linear("nd,df->nf", x, -1, up, 0, operands)
    return linear("nf,fd->nd", hidden, -1, down, 0, operands)


def routing(scores, cfg, follow=None, tie=0.0):
    """``scores`` [N, E] -> ``(weights [N, k], chosen [N, k], flipped
    [N], margin [N])``: the ``num_experts_per_tok`` largest a token.
    With ``follow`` [N, k], the experts the program took, one of them
    counts ``tie`` more in the choice (not in its weight).  ``flipped``:
    the choice is not the scores' own; ``margin``: how far the lowest
    score of ``follow`` lies below the scores' own cut."""
    import jax
    import jax.numpy as jnp
    k = cfg["num_experts_per_tok"]
    own_top, own = jax.lax.top_k(scores, k)
    chosen, flipped = own, jnp.zeros(scores.shape[0], bool)
    margin = jnp.zeros(scores.shape[0], scores.dtype)
    if follow is not None:
        taken = jnp.zeros(scores.shape, bool).at[
            jnp.arange(scores.shape[0])[:, None], follow].set(True)
        _, chosen = jax.lax.top_k(scores + tie * taken, k)
        mine = jnp.zeros(scores.shape, bool).at[
            jnp.arange(scores.shape[0])[:, None], own].set(True)
        picked = jnp.zeros(scores.shape, bool).at[
            jnp.arange(scores.shape[0])[:, None], chosen].set(True)
        flipped = jnp.any(picked != mine, -1)
        margin = jnp.maximum(own_top[:, -1] - jnp.min(
            jnp.take_along_axis(scores, follow, -1), -1), 0.0)
    top = jnp.take_along_axis(scores, chosen, -1)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return top * cfg["routed_scaling_factor"], chosen, flipped, margin


def experts_share(w, x, cfg, held, operands=None, follow=None, tie=0.0,
                  seen=None):
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


def experts(layer, x, cfg, operands=None, **routed):
    w = layer["moe"]
    h = reference.rms_norm(x, layer["mlp_norm"]["scale"].astype("float32"),
                           cfg["rms_norm_eps"])
    flat = h.reshape(-1, h.shape[-1])
    shared = gated_mlp(flat, *(w[name]["kernel"].astype("float32")
                               for name in ("shared_gate", "shared_up",
                                            "shared_down")), operands)
    routed = experts_share(w, flat, cfg, cfg["experts_held"], operands,
                           **routed)
    return x + (shared + routed).reshape(x.shape)


def attention(layer, x, cfg, operands=None):
    import jax
    import jax.numpy as jnp
    h = reference.rms_norm(x, layer["mixer_norm"]["scale"],
                           cfg["rms_norm_eps"])
    attn = layer["attn"]
    q, k, v, gate = (linear("btd,dhk->bthk", h, -1, attn[name]["kernel"], 0,
                            operands) for name in ("wq", "wk", "wv", "wg"))
    t, kv = x.shape[1], k.shape[2]
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = cfg["head_dim"] ** -0.5

    def group(args):                       # one key-value head's queries
        qg, kg, vg = args                  # [B, T, G, D], [B, T, D] x 2
        scores = scale * jnp.einsum("bqgk,bsk->bgqs", qg, kg)
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("bgqs,bsk->bqgk", jax.nn.softmax(scores, -1), vg)

    grouped = q.reshape(*q.shape[:2], kv, -1, q.shape[-1])
    mixed = jax.lax.map(group, (jnp.moveaxis(grouped, 2, 0),
                                jnp.moveaxis(k, 2, 0),
                                jnp.moveaxis(v, 2, 0)))
    mixed = jnp.moveaxis(mixed, 0, 2).reshape(q.shape)
    return x + linear("bthk,hkd->btd", mixed * jax.nn.sigmoid(gate),
                      (-2, -1), attn["wo"]["kernel"], (0, 1), operands)


def recurrence(q, k, v, g, beta, state_at=None, state_dtype=None):
    """The delta rule, one position at a time, from an empty state:
    ``q, k, v, g`` [B, T, H, D], ``beta`` [B, T, H] -> ``(o [B, T, H, D],
    the state after ``state_at`` positions [B, H, K, V])``; ``state_at``
    may be traced, and ``state_dtype`` rounds the state to it after every
    position."""
    import jax
    import jax.numpy as jnp

    def step(carry, at):
        state, kept = carry
        pos, q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None] * state               # Diag(a) S
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, b_t[..., None] * (v_t - read))
        if state_dtype is not None:     # a cast there and back may be
            bits = jnp.finfo(state_dtype)   # compiled away: excess precision
            state = jax.lax.reduce_precision(state, bits.nexp, bits.nmant)
        if state_at is not None:
            kept = jnp.where(pos + 1 == state_at, state, kept)
        return (state, kept), jnp.einsum("bhkv,bhk->bhv", state, q_t)

    b, t, heads, d = k.shape
    empty = jnp.zeros((b, heads, d, v.shape[-1]), jnp.float32)
    (_, kept), o = jax.lax.scan(
        step, (empty, empty), (jnp.arange(t), *(
            jnp.moveaxis(each, 1, 0) for each in (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1), kept


def kda(layer, x, cfg, operands=None, state_at=None, state_dtype=None):
    """The delta-rule mixer over whole sequences.  ``state_at`` (it may
    be traced) also returns the state after ``state_at`` positions;
    ``state_dtype`` rounds the state to it after every position (the
    control)."""
    import jax
    import jax.numpy as jnp
    own = cfg["linear_attn_config"]
    heads, d, width = own["num_heads"], own["head_dim"], \
        own["short_conv_kernel_size"]
    inner, rank = heads * d, cfg["kda_gate_rank"]
    w = layer["kda"]
    h = reference.rms_norm(x, layer["mixer_norm"]["scale"],
                           cfg["rms_norm_eps"])
    proj = linear("btd,df->btf", h, -1, w["in_proj"]["kernel"], 0, operands)
    qkv, decay, gate, beta = jnp.split(
        proj, [3 * inner, 3 * inner + rank, 3 * inner + 2 * rank], axis=-1)
    t = x.shape[1]
    padded = jnp.pad(qkv, [(0, 0), (width - 1, 0), (0, 0)])
    qkv = jax.nn.silu(sum(padded[:, i:i + t] * w["conv_kernel"][i]
                          for i in range(width)))
    q, k, v = (each.reshape(*each.shape[:2], heads, d)
               for each in jnp.split(qkv, 3, axis=-1))
    q, k = (each / jnp.sqrt(jnp.sum(each * each, -1, keepdims=True) + L2_EPS)
            for each in (q, k))
    q = q * d ** -0.5
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        linear("btr,rf->btf", decay, -1, w["decay_up"]["kernel"], 0,
               operands) + w["dt_bias"]).reshape(*x.shape[:2], heads, d)
    beta = 2.0 * jax.nn.sigmoid(beta)                         # [B, T, H]

    o, kept = recurrence(q, k, v, g, beta, state_at, state_dtype)
    o = reference.rms_norm(o, w["norm"]["scale"],
                           cfg["rms_norm_eps"])               # a head
    gate = linear("btr,rf->btf", gate, -1, w["gate_up"]["kernel"], 0,
                  operands).reshape(o.shape)
    out = x + linear("btf,fd->btd",
                     (o * jax.nn.sigmoid(gate)).reshape(*x.shape[:2], inner),
                     -1, w["out_proj"]["kernel"], 0, operands)
    return out if state_at is None else (out, kept)


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
    for i, kind in enumerate(cfg["layer_types"]):
        layer = params[f"layer_{i}"]
        x = (kda if kind == "kda" else attention)(layer, x, cfg, operands)
        x = experts(layer, x, cfg, operands)
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
    d, ff, vocab = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["vocab_size"]
    q_heads, kv_heads, head_dim = cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    own = cfg["linear_attn_config"]
    heads, width = own["num_heads"], own["short_conv_kernel_size"]
    inner, rank = heads * own["head_dim"], cfg["kda_gate_rank"]
    first, count = held or cfg["experts_held"]
    shared_ff = cfg["n_shared_experts"] * ff
    # (law, shape, fan-in); the recurrence's own laws by name.
    normal = lambda fan_in, *shape: ("normal", shape, fan_in)   # noqa: E731
    shared = {"mixer_norm": {"scale": ("ones", (d,), 0)},
              "mlp_norm": {"scale": ("ones", (d,), 0)},
              "moe": {"router": normal(d, d, cfg["router_experts"]),
                      "shared_gate": {"kernel": normal(d, d, shared_ff)},
                      "shared_up": {"kernel": normal(d, d, shared_ff)},
                      "shared_down": {"kernel": normal(shared_ff,
                                                       shared_ff, d)}}}
    expert = {"experts_gate": normal(d, d, ff), "experts_up": normal(d, d, ff),
              "experts_down": normal(ff, ff, d)}
    kinds = {
        "attention": {"attn": {
            "wq": {"kernel": normal(d, d, q_heads, head_dim)},
            "wk": {"kernel": normal(d, d, kv_heads, head_dim)},
            "wv": {"kernel": normal(d, d, kv_heads, head_dim)},
            "wg": {"kernel": normal(d, d, q_heads, head_dim)},
            "wo": {"kernel": normal(q_heads * head_dim, q_heads, head_dim,
                                    d)}}},
        "kda": {"kda": {
            "in_proj": {"kernel": normal(d, d, 3 * inner + 2 * rank + heads)},
            "decay_up": {"kernel": normal(rank, rank, inner)},
            "gate_up": {"kernel": normal(rank, rank, inner)},
            "out_proj": {"kernel": normal(inner, inner, d)},
            "conv_kernel": normal(width, width, 3 * inner),
            "A_log": ("a_log", (heads,), 0),
            "dt_bias": ("dt_bias", (inner,), 0),
            "norm": {"scale": ("ones", (own["head_dim"],), 0)}}}}
    outer = {"embed": {"embedding": normal(d, vocab, d)},
             "final_norm": {"scale": ("ones", (d,), 0)},
             "lm_head": {"kernel": normal(d, d, vocab)}}

    def draw(key, law, shape, fan_in):
        if law == "ones":
            return jnp.ones(shape, dtype)
        if law == "normal":
            return (fan_in ** -0.5 * jax.random.normal(
                key, shape, jnp.float32)).astype(dtype)
        if law == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        step = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return step + jnp.log(-jnp.expm1(-step))     # inverse softplus

    def maker(tree):
        """One compiled program for a tree of laws, called with each
        layer's (or each expert's) key."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, tuple))
        return jax.jit(lambda key: jax.tree_util.tree_unflatten(treedef, [
            draw(jax.random.fold_in(key, at), *spec)
            for at, (_, spec) in enumerate(flat)]))

    key = jax.random.key(run.seed)
    layer = {kind: maker({**shared, **own_}) for kind, own_ in kinds.items()}
    an_expert = maker(expert)
    stack = jax.jit(lambda *each: jnp.stack(each))
    params = maker(outer)(jax.random.fold_in(key, 0))
    for i, kind in enumerate(cfg["layer_types"]):
        layer_key = jax.random.fold_in(key, 1 + i)
        made = layer[kind](layer_key)
        held_here = [an_expert(jax.random.fold_in(layer_key, 1000 + e))
                     for e in range(first, first + count)]
        made["moe"].update({name: stack(*(e[name] for e in held_here))
                            for name in expert})
        params[f"layer_{i}"] = made
    return params


# ------------------------------------------------- what every run compares
def replay(cfg: dict):
    """The program, replayed on one stream it served: a function of
    ``(params, tokens [1, T], first, length)`` that prefills the prompt
    ``tokens[0, :first]`` as the replica does (a batch of one, padded to
    a bucket, here the widest, the true length passed) and feeds
    ``tokens[0, first:length - 1]`` to the family's decode step, one
    token at a time in a cache of one slot, as it did when it served
    them.  It returns the
    program's ``logits`` [T, vocab] (position t predicts token t + 1;
    rows outside ``first - 1 .. length - 2`` are zeros), the experts
    each token took, ``chosen`` [layers, T, k], the slot's final
    delta-rule ``states``, {layer: [H, K, V]}, and what each KDA layer's
    recurrence was ``fed`` at every position, {layer: {"k", "v", "g" [1,
    T, H, D], "beta" [1, T, H]}} (past ``length - 2`` whatever the
    prompt's padding left).  The model is built from the configuration's
    file as ``run.py`` builds it."""
    import jax
    import jax.numpy as jnp
    import run as harness

    config = harness.resolve(cfg["model"]["config"])(**{
        **harness.build_args(cfg), "decode": True,
        "max_seq_len": cfg["serve"]["max_seq"]})
    family = config.family
    model = family.build(config)
    layers = range(len(cfg["layer_types"]))
    per_token = cfg["num_experts_per_tok"]
    bucket = max(cfg["serve"]["warmup_buckets"])     # one shape for all

    delta = [i for i in layers if cfg["layer_types"][i] == "kda"]
    FED = ("k", "v", "g", "beta")

    def took(sown):                        # [layers, N, k]
        return jnp.stack([sown["routing"][f"layer_{i}"]["moe"]["chosen"][0]
                          for i in layers])

    def fed_to(sown):                      # name -> [KDA layers, B, T, ...]
        return {name: jnp.stack([
            sown["recurrence"][f"layer_{i}"]["kda"][name][0] for i in delta])
            for name in FED}

    def run(params, tokens, first, length):
        variables = {"params": params}
        positions = tokens.shape[1]
        prompt = jnp.where(jnp.arange(bucket) < first, tokens[:, :bucket], 0)
        sown = {"routing": {}, "recurrence": {}}
        logits, cache = family.prefill(model, variables, prompt,
                                       lengths=first, sown=sown)
        chosen = jnp.zeros((len(layers), positions, per_token), jnp.int32) \
            .at[:, :bucket].set(took(sown))
        rows = jnp.zeros((positions, logits.shape[-1]), jnp.float32) \
            .at[first - 1].set(logits[0, first - 1].astype(jnp.float32))
        fed = {name: jnp.zeros((*value.shape[:2], positions,
                                *value.shape[3:]), jnp.float32)
               .at[:, :, :bucket].set(value)
               for name, value in fed_to(sown).items()}

        def step(at, carry):
            cache, chosen, rows, fed = carry
            sown = {"routing": {}, "recurrence": {}}
            logits, cache = family.decode_step(
                model, variables, cache,
                jax.lax.dynamic_slice_in_dim(tokens, at, 1, axis=1),
                sown=sown)
            now = fed_to(sown)
            return (cache, chosen.at[:, at].set(took(sown)[:, 0]),
                    rows.at[at].set(logits[0, 0].astype(jnp.float32)),
                    {name: fed[name].at[:, :, at].set(now[name][:, :, 0])
                     for name in FED})

        cache, chosen, rows, fed = jax.lax.fori_loop(
            first, length - 1, step, (cache, chosen, rows, fed))
        states = {i: cache[f"layer_{i}"]["kda"]["kda_state"][0]
                  for i in delta}
        return {"logits": rows, "chosen": chosen, "states": states,
                "fed": {i: {name: fed[name][at] for name in FED}
                        for at, i in enumerate(delta)}}

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
    layer at a time: each kind of mixer and the expert block are jitted
    functions that are handed their layer's weights and cast them to
    float32 themselves (an expert inside the loop over the experts), so
    that one mixer and one expert are on the device in float32 at once,
    not 13 GB of model."""
    import jax
    import jax.numpy as jnp

    tie = cfg["served_check"]["tie"]
    replayed = replay(cfg)

    def full(tree):
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)

    def mixer_fn(kind, operands, state_dtype):
        def run_mixer(layer, x, state_at):
            with jax.default_matmul_precision("highest"):
                if kind == "attention":
                    return attention(full(layer), x, cfg, operands), ()
                return kda(full(layer), x, cfg, operands, state_at,
                           state_dtype)
        return jax.jit(run_mixer)

    def experts_fn(operands):
        def run_experts(layer, x, follow):  # the experts cast one by one
            seen = {}
            with jax.default_matmul_precision("highest"):
                x = experts(layer, x, cfg, operands, follow=follow,
                            tie=tie, seen=seen)
            return x, seen
        return jax.jit(run_experts)

    def head_fn(operands):
        def run_head(outer, x):
            with jax.default_matmul_precision("highest"):
                return head(full(outer), x, cfg, operands)[0]
        return jax.jit(run_head)

    # name -> (what rounds a linear map's operands, the state's type)
    passes = {"": (None, None), **({"control_": (reference.int8,
                                                 jnp.bfloat16)}
                                   if control else {})}
    mixers = {(kind, name): mixer_fn(kind, *how)
              for kind in KINDS for name, how in passes.items()}
    blocks = {name: experts_fn(how[0]) for name, how in passes.items()}
    heads = {name: head_fn(how[0]) for name, how in passes.items()}

    def forward(name, params, tokens, follow, state_at):
        """One pass of the reference -> (logits [T, vocab], the final
        states, tokens whose routing is not the scores' own a layer,
        the widest margin of a followed choice)."""
        outer = {key: params[key]
                 for key in ("embed", "final_norm", "lm_head")}
        x = embed(outer, tokens, cfg).astype(jnp.float32)
        states, flipped, margin = {}, 0, 0.0
        for i, kind in enumerate(cfg["layer_types"]):
            layer = params[f"layer_{i}"]
            x, state = mixers[kind, name](
                {key: value for key, value in layer.items()
                 if key != "moe"}, x, state_at)
            if kind == "kda":
                states[i] = state[0]
            x, seen = blocks[name](
                {key: layer[key] for key in ("mlp_norm", "moe")}, x,
                follow[i])
            flipped = flipped + seen["flipped"]
            margin = jnp.maximum(margin, seen["margin"])
        return heads[name](outer, x), states, flipped, margin

    @jax.jit
    def read(logits_, served, chosen, live):
        """(widest, sum) of the gaps of ``chosen`` below the best of
        ``logits_`` and the count of ``chosen`` that are not ``served``,
        over the live positions."""
        below = jnp.where(live, jnp.max(logits_, -1) - jnp.take_along_axis(
            logits_, chosen[:, None], -1)[:, 0], 0.0)
        return jnp.max(below), jnp.sum(below), \
            jnp.sum(live & (chosen != served))

    def reached(state_dtype):
        def scan(fed, state_at):
            """The state that the recurrence reaches from what the
            program fed it, in float32 (or ``state_dtype``)."""
            with jax.default_matmul_precision("highest"):
                return {i: recurrence(
                    jnp.zeros_like(of["k"]), of["k"], of["v"], of["g"],
                    of["beta"], state_at, state_dtype)[1][0]
                    for i, of in fed.items()}
        return jax.jit(scan)

    reach = {name: reached(how[1]) for name, how in passes.items()}

    @jax.jit
    def apart(got, want):
        """The norm of the difference over the norm, the worst layer."""
        return jnp.max(jnp.stack([
            jnp.linalg.norm(got[i] - want[i]) / jnp.linalg.norm(want[i])
            for i in want]))

    @jax.jit
    def off(got, want, live):
        """The root mean square over the vocabulary of the difference,
        over that of ``want``, the worst live position."""
        rms = lambda x: jnp.sqrt(jnp.mean(x * x, -1))        # noqa: E731
        return jnp.max(jnp.where(live, rms(got - want) / rms(want), 0.0))

    def gaps(params, tokens, first, length):
        tokens = jnp.asarray(tokens)
        at = jnp.arange(tokens.shape[1])
        live = (at >= first - 1) & (at < length - 1)   # t predicts t + 1
        served = jnp.roll(tokens[0], -1)
        program = replayed(params, tokens, first, length)
        put_first = jnp.argmax(program["logits"], -1)
        logits_, states, flipped, margin = forward(
            "", params, tokens, program["chosen"], length - 1)
        _, gap_sum, _ = read(logits_, served, served, live)
        gap, _, missed = read(logits_, served, put_first, live)
        carried = reach[""](program["fed"], length - 1)
        seen = {"gap": gap, "gap_sum": gap_sum, "replay_miss_sum": missed,
                "state_gap": apart(program["states"], carried),
                "state_err": apart(program["states"], states),
                "replay_err": off(program["logits"], logits_, live),
                "route_margin": jnp.max(jnp.where(live, margin, 0.0)),
                "route_flips_sum": jnp.sum(jnp.where(live, flipped, 0))}
        if control:
            lower, _, _, _ = forward(
                "control_", params, tokens, program["chosen"], length - 1)
            gap, gap_sum, missed = read(logits_, served,
                                        jnp.argmax(lower, -1), live)
            seen.update({"control_gap": gap, "control_gap_sum": gap_sum,
                         "control_replay_miss_sum": missed,
                         "control_replay_err": off(lower, logits_, live),
                         "control_state_gap": apart(
                             reach["control_"](program["fed"], length - 1),
                             carried)})
        return seen

    return gaps


# -------------------------------------------------------- --check reference
def check(run, cfg: dict) -> dict:
    """Prefill one prompt as the executor does (a batch of one, padded to
    its bucket, the true length passed), insert it into a slot of a slot
    cache, then decode through the cache; every row against ``logits``.
    The prompt ends inside a chunk of the scan, so the state has to
    cross a chunk's edge and stop short of the padding."""
    import jax
    import jax.numpy as jnp

    kinds = ("kda", "attention")
    positions, prompt, decoded = 512, 300, 8
    slots, slot = 4, 3
    cfg = {**cfg, "layer_types": kinds}
    config = run.model_config(layer_types=kinds, decode=True,
                              max_seq_len=positions)
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

"""The plain reference of ``MiMo-V2.5`` (Xiaomi MiMo, ``model_type``
``mimo_v2``, family "MiMo-V2-Flash 309B-A15B"), as one chip of its
deployment computes it, with its seeded weights and its checks.

``logits`` is the forward pass as the published ``config.json`` gives it
(ISSUE 36 has the equations), in straightforward ``jax.numpy`` and
float32 (callers set ``jax.default_matmul_precision("highest")``), with
no kernel, cache, ring or sort, and shares nothing with
``horovod_tpu/models`` but the names of the parameter tree it is handed
(``x`` a block's normalised input, RMSNorm eps ``layernorm_epsilon``):

    h = E[token]
    layer l:  h = h + Attention_l(RMSNorm(h))
              h = h + FFN_l(RMSNorm(h))
    logits = W_head RMSNorm(h)            (untied head)

``Attention_l``, both kinds: ``num_attention_heads`` query heads, keys
and queries ``head_dim`` wide, values ``v_head_dim``; rotary positions
on the first ``rotary_dim = int(head_dim * partial_rotary_factor)``
channels of every query and key head, pairs ``(i, i + rotary_dim / 2)``,
frequencies ``base^(-2i / rotary_dim)``, the other channels as they are;
scores ``q_i . k_j / sqrt(head_dim)``, query head ``h`` reading
key-value head ``h // group``; ``o_i = sum_j p_ij (attention_value_scale
* v_j)``.  Where ``layer_types[l] == "attention"`` (global):
``num_key_value_heads``, base ``rope_theta``, key ``j`` visible at ``i``
when ``j <= i``, no sink.  Where it is ``"window"``:
``swa_num_key_value_heads``, base ``swa_rope_theta``, key ``j`` visible
when ``i - sliding_window < j <= i``, and a learned scalar ``b_h`` a
query head takes part in the softmax as **one more column that carries
no value**: a full masked softmax over ``[scores, b_h]`` whose last
column is dropped.  A key-value head and a block of query positions at
a time, only so that 12,288 positions fit.

``FFN_l`` where ``l`` is in ``dense_layers``: ``W_down (silu(W_gate x) *
W_up x)`` at ``intermediate_size``.  Elsewhere: ``s = sigmoid(W_r x)``
over all ``router_experts`` of the model, the ``num_experts_per_tok``
largest of ``s + c`` (``c`` the router's correction bias, float32;
``topk_method: noaux_tc`` with one group), weights ``s_e / sum of the
chosen s`` (``norm_topk_prob``; ``c`` is not in them) times
``routed_scaling_factor`` (null: 1), ``y = sum_e w_e E_e(x)`` at
``moe_intermediate_size``, no shared expert.  **The chip's share**: the
sum runs over the chosen experts among ``experts_held`` (first, count),
as a loop over those experts, each computed for every token and masked;
what the other chips' experts would add is left out, here as in the
program, and the partial result goes on to the next layer (the guide's
section 4).  ``experts_share`` computes any one share, for the test that
adds all of them up.

Departures from the published description: the weights are seeded
random ones (``weights``), since nothing can be downloaded here; the
checkpoint's fused projection (``attention_projection_layout``) is three
matrices, which changes no number; the vision and audio towers and the
multi-token-prediction layers are left out (the configuration's file
says why).  The sizes the file lists under ``assumed`` are read from it.
``linear``, ``gated_mlp``, ``routing`` (the choice where scores tie) and
the int8 rounding are ``solar_open2_reference.py``'s and
``reference.py``'s own functions, imported.

``weights`` makes the tree the replica is handed: bfloat16, a normal law
of variance one over the fan-in for every linear map, the embedding and
the router; the sinks a normal law of variance 1 and the correction
bias uniform on -0.05 to 0.05 (float32 both: the choice then differs
from the order of the scores, and a program that puts ``c`` into the
weights fails), ones for the norms.

``served_gap`` is the comparison every run of the cell makes, by
``solar_open2_reference.py``'s method: each sampled stream is **replayed
through the program** (``replay``: the prompt prefilled as the replica
does it, every served token fed to the family's decode step in a cache
of one slot) and the reference **follows the program's routing where
its own ``s + c`` tie within ``served_check.tie``**.  With random
weights a token's logits hardly depend on its attention layers (their
outputs average a hundred values and are a hundredth of the residual
stream's variance), so the logits alone cannot tell a window of 127
from one of 128: the replay also collects what each attention layer's
softmax was fed and what came out of it, at ``served_check.
attend_samples`` positions of the stream, and what each router scored,
chose and weighed.  It reads

- ``gap_mean``, ``gap``, ``replay_miss_mean``, ``replay_err``: as
  ``solar_open2_reference.py`` reads them (the served tokens' gaps under
  the reference's best, the widest of the token the replay puts first,
  the share the replay does not reproduce, the program's logits against
  the reference's at every position);
- ``attend_gap``: the program's attention output against the float32
  masked softmax (with its sink and window, the reference's rotary
  positions, the value scale) over **what the program fed its own**
  (the queries, keys and values as its projections gave them), the norm
  of the difference over the norm, the worst layer: the attention's
  arithmetic alone: the window's edges, the ring, the sink, the two
  rotary bases and widths, the value scale, the kernel;
- ``route_gap``: the weights the program gave its chosen experts against
  ``s_e / sum`` over the program's own scores, the widest difference,
  and 1 where a token's choice is not the ``num_experts_per_tok``
  largest of its own ``s + c``: the router's rule alone.

Its control is the reference with both operands of every linear map
rounded to 8 bits (``reference.int8``).  ``check`` is ``--check
reference``.
"""
from __future__ import annotations

import reference
import solar_open2_reference as solar
from solar_open2_reference import gated_mlp, linear

TOLERANCE = 0.025
ATTEND_BLOCK = 512       # query positions the reference holds at a time


# ------------------------------------------------------------- the equations
def kind_of(cfg: dict, kind: str) -> dict:
    """A layer kind's own sizes: key-value heads, rotary base, window (0:
    every position), whether it has a sink."""
    if kind == "window":
        return {"kv": cfg["swa_num_key_value_heads"],
                "theta": cfg["swa_rope_theta"],
                "window": cfg["sliding_window"],
                "sink": cfg["add_swa_attention_sink_bias"]}
    return {"kv": cfg["num_key_value_heads"], "theta": cfg["rope_theta"],
            "window": 0, "sink": cfg["add_full_attention_sink_bias"]}


def rotary(x, positions, width: int, theta: float):
    """Rotary positions on the first ``width`` channels of ``x`` [B, T, H,
    D] at ``positions`` [T]: pairs ``(i, i + width / 2)``."""
    import jax.numpy as jnp
    half = width // 2
    angle = positions[:, None].astype(jnp.float32) \
        * theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / width)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:width], x[..., width:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, rest], -1)


def softmax_attention(q, k, v, sink, cfg: dict, own: dict, q_at=None):
    """The full masked softmax: ``q`` [B, Tq, H, D] at positions ``q_at``
    [Tq] (every position where None) over ``k`` [B, T, KV, D] and ``v``
    [B, T, KV, Dv] at positions 0 to T - 1, all before their rotary
    positions -> [B, Tq, H, Dv].  The sink is an explicit extra column."""
    import jax
    import jax.numpy as jnp
    t, kv = k.shape[1], k.shape[2]
    q_at = jnp.arange(t) if q_at is None else q_at
    width = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    q = rotary(q, q_at, width, own["theta"])
    k = rotary(k, jnp.arange(t), width, own["theta"])
    v = cfg["attention_value_scale"] * v
    scale = cfg["head_dim"] ** -0.5
    group = q.shape[2] // kv
    block = min(ATTEND_BLOCK, q.shape[1])
    pad = -q.shape[1] % block
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q_at = jnp.pad(q_at, (0, pad))
    blocks = q.shape[1] // block
    grouped = q.reshape(q.shape[0], blocks, block, kv, group, q.shape[-1])
    at = q_at.reshape(blocks, block)

    def one(args):                 # a key-value head's queries, one block
        qg, kg, vg, bias, pos = args       # [B, block, G, D], [B, T, D] x 2
        scores = scale * jnp.einsum("bqgk,bsk->bgqs", qg, kg)
        seen = jnp.arange(t)[None, :] <= pos[:, None]
        if own["window"]:
            seen &= pos[:, None] - jnp.arange(t)[None, :] < own["window"]
        scores = jnp.where(seen, scores, -jnp.inf)
        if own["sink"]:
            column = jnp.broadcast_to(bias[None, :, None, None],
                                      (*scores.shape[:-1], 1))
            weights = jax.nn.softmax(
                jnp.concatenate([scores, column], -1), -1)[..., :-1]
        else:
            weights = jax.nn.softmax(scores, -1)
        return jnp.einsum("bgqs,bsk->bqgk", weights, vg)

    sinks = jnp.zeros((kv, group)) if sink is None \
        else sink.reshape(kv, group)

    def a_head(args):
        qh, kh, vh, bias = args            # qh [blocks, B, block, G, D]
        return jax.lax.map(lambda each: one((each[0], kh, vh, bias,
                                             each[1])), (qh, at))

    mixed = jax.lax.map(a_head, (
        jnp.transpose(grouped, (3, 1, 0, 2, 4, 5)), jnp.moveaxis(k, 2, 0),
        jnp.moveaxis(v, 2, 0), sinks))     # [KV, blocks, B, block, G, Dv]
    mixed = jnp.transpose(mixed, (2, 1, 3, 0, 4, 5))
    return mixed.reshape(q.shape[0], blocks * block, kv * group,
                         v.shape[-1])[:, :q.shape[1] - pad]


def attention(layer, x, cfg: dict, kind: str, operands=None):
    h = reference.rms_norm(x, layer["mixer_norm"]["scale"],
                           cfg["layernorm_epsilon"])
    attn = layer["attn"]
    q, k, v = (linear("btd,dhk->bthk", h, -1, attn[name]["kernel"], 0,
                      operands) for name in ("wq", "wk", "wv"))
    mixed = softmax_attention(q, k, v, attn.get("sink"), cfg,
                              kind_of(cfg, kind))
    return x + linear("bthk,hkd->btd", mixed, (-2, -1),
                      attn["wo"]["kernel"], (0, 1), operands)


def router_weights(scores, chosen, cfg: dict):
    """The weights of the ``chosen`` experts [N, k] from a router's
    ``scores`` [N, E]: ``s_e`` over the sum of the chosen
    (``norm_topk_prob``) times ``routed_scaling_factor`` (null: 1); the
    correction bias is not in them."""
    import jax.numpy as jnp
    top = jnp.take_along_axis(scores, chosen, -1)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return top * (cfg["routed_scaling_factor"] or 1.0)


def experts_share(w, x, cfg: dict, held, operands=None, follow=None,
                  tie=0.0, seen=None):
    """``x`` [N, d], normalised -> what the chip holding the experts
    ``held = (first, count)`` adds for them: ``sum_e w_e E_e(x)`` over
    the chosen experts among its own.  ``w`` has that chip's expert
    weights, ``[count, ...]``, and the whole router with its bias.
    ``follow``, ``tie``: see ``solar_open2_reference.routing``, which
    makes the choice here over ``s + c``, and whose ``flipped`` and
    ``margin`` a dict given as ``seen`` receives."""
    import jax
    import jax.numpy as jnp
    first, count = held
    scores = jax.nn.sigmoid(linear(
        "nd,de->ne", x, -1, w["router"].astype(jnp.float32), 0, operands))
    _, chosen, flipped, margin = solar.routing(
        scores + w["router_bias"], {**cfg, "routed_scaling_factor": 1.0},
        follow, tie)
    top = router_weights(scores, chosen, cfg)
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
    """The dense MLP where the layer has one, else this chip's share of
    the expert block."""
    h = reference.rms_norm(x, layer["mlp_norm"]["scale"].astype("float32"),
                           cfg["layernorm_epsilon"])
    flat = h.reshape(-1, h.shape[-1])
    if "mlp" in layer:
        out = gated_mlp(flat, *(layer["mlp"][name]["kernel"].astype("float32")
                                for name in ("gate", "up", "down")), operands)
        if routed.get("seen") is not None:
            routed["seen"].update(flipped=0, margin=0.0)
    else:
        out = experts_share(layer["moe"], flat, cfg, cfg["experts_held"],
                            operands, **routed)
    return x + out.reshape(x.shape)


def embed(params, tokens, cfg):
    return params["embed"]["embedding"][tokens]


def head(params, x, cfg, operands=None):
    x = reference.rms_norm(x, params["final_norm"]["scale"],
                           cfg["layernorm_epsilon"])
    return linear("btd,dv->btv", x, -1, params["lm_head"]["kernel"], 0,
                  operands)


def logits(params, tokens, cfg: dict, operands=None):
    """tokens [B, T] -> logits [B, T, vocab], float32."""
    x = embed(params, tokens, cfg)
    for i, kind in enumerate(cfg["layer_types"]):
        layer = params[f"layer_{i}"]
        x = attention(layer, x, cfg, kind, operands)
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
    heads, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], \
        cfg["v_head_dim"]
    first, count = held or cfg["experts_held"]
    normal = lambda fan_in, *shape: ("normal", shape, fan_in)   # noqa: E731
    norms = {"mixer_norm": {"scale": ("ones", (d,), 0)},
             "mlp_norm": {"scale": ("ones", (d,), 0)}}

    def attn(kind):
        own = kind_of(cfg, kind)
        made = {"wq": {"kernel": normal(d, d, heads, dk)},
                "wk": {"kernel": normal(d, d, own["kv"], dk)},
                "wv": {"kernel": normal(d, d, own["kv"], dv)},
                "wo": {"kernel": normal(heads * dv, heads, dv, d)}}
        if own["sink"]:
            made["sink"] = ("sink", (heads,), 0)
        return made

    def ffn(dense, ff):
        if dense:
            return {"mlp": {"gate": {"kernel": normal(d, d, ff)},
                            "up": {"kernel": normal(d, d, ff)},
                            "down": {"kernel": normal(ff, ff, d)}}}
        return {"moe": {"router": normal(d, d, cfg["router_experts"]),
                        "router_bias": ("bias", (cfg["router_experts"],),
                                        0)}}

    ff = cfg["moe_intermediate_size"]
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
        if law == "sink":
            return jax.random.normal(key, shape, jnp.float32)
        return jax.random.uniform(key, shape, jnp.float32, -0.05, 0.05)

    def maker(tree):
        """One compiled program for a tree of laws, called with each
        layer's (or each expert's) key."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, tuple))
        return jax.jit(lambda key: jax.tree_util.tree_unflatten(treedef, [
            draw(jax.random.fold_in(key, at), *spec)
            for at, (_, spec) in enumerate(flat)]))

    key = jax.random.key(run.seed)
    makers: dict = {}
    an_expert = maker(expert)
    stack = jax.jit(lambda *each: jnp.stack(each))
    params = maker(outer)(jax.random.fold_in(key, 0))
    for i, kind in enumerate(cfg["layer_types"]):
        dense = i in cfg["dense_layers"]
        if (kind, dense) not in makers:
            makers[kind, dense] = maker({
                **norms, "attn": attn(kind),
                **ffn(dense, cfg["intermediate_size"])})
        layer_key = jax.random.fold_in(key, 1 + i)
        made = makers[kind, dense](layer_key)
        if not dense:
            held_here = [an_expert(jax.random.fold_in(layer_key, 1000 + e))
                         for e in range(first, first + count)]
            made["moe"].update({name: stack(*(e[name] for e in held_here))
                                for name in expert})
        params[f"layer_{i}"] = made
    return params


# ------------------------------------------------- what every run compares
def sample_positions(first, length, count: int):
    """``count`` positions of a stream whose served tokens start at
    ``first`` and end before ``length``, rising: a quarter of them on
    either side of the prompt's end (the prefill's last rows, and the
    decode steps that still read what the prefill left in the rings),
    the others spread evenly from 0 to ``length - 2``; and which of them
    stand for a position of their own (a short stream repeats some)."""
    import jax.numpy as jnp
    near = count // 4
    wanted = jnp.sort(jnp.concatenate([
        first - near // 4 + jnp.arange(near),
        (jnp.arange(count - near) * (length - 1)) // (count - near)]))
    wanted = jnp.clip(wanted, 0, length - 2)
    return wanted, jnp.append(wanted[1:] != wanted[:-1], True)


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
    [layers, T, E] and the ``weights`` [layers, T, k] it gave them; the
    keys and values every attention layer's softmax was fed, ``k`` and
    ``v`` {layer: [T, KV, D]}; and at the ``at`` [samples] positions of
    ``sample_positions`` the queries it was fed and what came out,
    ``q`` and ``out`` {layer: [samples, H, D]}.  The model is built from
    the configuration's file as ``run.py`` builds it."""
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

    def fed_to(sown, name, i):             # [T', heads, D] of layer i
        return sown["attention"][f"layer_{i}"]["attn"][name][0][0]

    def run(params, tokens, first, length):
        variables = {"params": params}
        positions = tokens.shape[1]
        at, _ = sample_positions(first, length, samples)
        slot_of = jnp.full(positions, samples, jnp.int32) \
            .at[at].set(jnp.arange(samples))     # the last of a repeat
        prompt = jnp.where(jnp.arange(bucket) < first, tokens[:, :bucket], 0)
        sown = {"routing": {}, "attention": {}}
        logits, cache = family.prefill(model, variables, prompt,
                                       lengths=first, sown=sown)
        rows = jnp.zeros((positions, logits.shape[-1]), jnp.float32) \
            .at[first - 1].set(logits[0, first - 1].astype(jnp.float32))

        def whole(value):          # [layers', bucket, ...] -> [.., T, ..]
            return jnp.zeros((value.shape[0], positions, *value.shape[2:]),
                             value.dtype).at[:, :bucket].set(value)

        def sampled(value):        # [bucket, ...] -> [samples + 1, ...]
            return jnp.zeros((samples + 1, *value.shape[1:]), value.dtype) \
                .at[slot_of[:bucket]].set(value)

        routing = {name: whole(value) for name, value in took(sown).items()}
        keys = {name: {i: whole(fed_to(sown, name, i)[None])[0]
                       for i in layers} for name in ("k", "v")}
        some = {name: {i: sampled(fed_to(sown, name, i)) for i in layers}
                for name in ("q", "out")}

        def step(pos, carry):
            cache, rows, routing, keys, some = carry
            sown = {"routing": {}, "attention": {}}
            logits, cache = family.decode_step(
                model, variables, cache,
                jax.lax.dynamic_slice_in_dim(tokens, pos, 1, axis=1),
                sown=sown)
            now = took(sown)
            return (cache, rows.at[pos].set(logits[0, 0].astype(jnp.float32)),
                    {name: routing[name].at[:, pos].set(now[name][:, 0])
                     for name in ROUTING},
                    {name: {i: keys[name][i].at[pos].set(
                        fed_to(sown, name, i)[0]) for i in layers}
                     for name in ("k", "v")},
                    {name: {i: some[name][i].at[slot_of[pos]].set(
                        fed_to(sown, name, i)[0]) for i in layers}
                     for name in ("q", "out")})

        cache, rows, routing, keys, some = jax.lax.fori_loop(
            first, length - 1, step, (cache, rows, routing, keys, some))
        return {"logits": rows, **routing, **keys,
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

    def mixer_fn(kind, operands):
        def run_mixer(layer, x):
            with jax.default_matmul_precision("highest"):
                return attention(full(layer), x, cfg, kind, operands)
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
    mixers = {(kind, name): mixer_fn(kind, how)
              for kind in set(kinds) for name, how in passes.items()}
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
        for i, kind in enumerate(kinds):
            layer = params[f"layer_{i}"]
            x = mixers[kind, name](
                {key: layer[key] for key in ("mixer_norm", "attn")}, x)
            x, seen = blocks[name](
                {key: value for key, value in layer.items()
                 if key in ("mlp_norm", "mlp", "moe")}, x,
                follow[routed.index(i)] if i in routed else None)
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

    def attended(kind):
        own = kind_of(cfg, kind)

        def gap(q, k, v, out, sink, at, counts):
            """The program's attention output at the sampled positions
            against the float32 softmax over what it was fed: the norm
            of the difference over the norm."""
            with jax.default_matmul_precision("highest"):
                want = softmax_attention(
                    *(each.astype(jnp.float32)[None] for each in (q, k, v)),
                    sink, cfg, own, at)[0]
            keep = counts[:, None, None]
            return jnp.linalg.norm(jnp.where(keep, out - want, 0.0)) \
                / jnp.linalg.norm(jnp.where(keep, want, 0.0))
        return jax.jit(gap)

    attend_gaps = {kind: attended(kind) for kind in set(kinds)}

    @jax.jit
    def routed_gap(scores, chosen, weights_, bias, live):
        """The widest difference of the program's weights from ``s_e /
        sum`` over its own scores; 1 where its choice is not the largest
        of its own ``s + c`` (the cut's ties apart)."""
        biased = scores + bias[:, None, :]
        cut = jax.lax.top_k(biased, chosen.shape[-1])[0][..., -1:]
        mine = jnp.take_along_axis(biased, chosen, -1)
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
        logits_, flipped, margin = forward("", params, tokens,
                                           program["chosen"])
        _, gap_sum, _ = read(logits_, served, served, live)
        gap, _, missed = read(logits_, served, put_first, live)
        sampled, counts = sample_positions(first, length, samples)
        attend_gap = max(float(attend_gaps[kind](
            program["q"][i], program["k"][i], program["v"][i],
            program["out"][i], params[f"layer_{i}"]["attn"].get("sink"),
            sampled, counts)) for i, kind in enumerate(kinds))
        bias = jnp.stack([params[f"layer_{i}"]["moe"]["router_bias"]
                          for i in routed])
        seen = {"gap": gap, "gap_sum": gap_sum, "replay_miss_sum": missed,
                "replay_err": off(program["logits"], logits_, live),
                "attend_gap": attend_gap,
                "route_gap": routed_gap(program["scores"], program["chosen"],
                                        program["weights"], bias, live),
                "route_margin": jnp.max(jnp.where(live, margin, 0.0)),
                "route_flips_sum": jnp.sum(jnp.where(live, flipped, 0))}
        if control:
            lower, _, _ = forward("control_", params, tokens,
                                  program["chosen"])
            gap, gap_sum, missed = read(logits_, served,
                                        jnp.argmax(lower, -1), live)
            seen.update({"control_gap": gap, "control_gap_sum": gap_sum,
                         "control_replay_miss_sum": missed,
                         "control_replay_err": off(lower, logits_, live),
                         # The control is of the reference's linear maps:
                         # what the program fed its own is not its to
                         # round.
                         "control_attend_gap": attend_gap,
                         "control_route_gap": seen["route_gap"]})
        return seen

    return gaps


# -------------------------------------------------------- --check reference
def check(run, cfg: dict) -> dict:
    """Prefill one prompt as the executor does (a batch of one, padded to
    its bucket, the true length passed), insert it into a slot of a slot
    cache, then decode through the cache; every row against ``logits``.
    The dense layer and both kinds of attention layer; the prompt is
    longer than the window and ends inside its bucket, so the rings have
    gone round and hold no padding, and the decode steps go round once
    more."""
    import jax
    import jax.numpy as jnp

    kinds = ("attention", "window", "attention")
    window = cfg["sliding_window"]
    positions = 512 if window > 64 else 64
    prompt, decoded = positions * 5 // 8 - 3, min(window + 3, positions // 4)
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

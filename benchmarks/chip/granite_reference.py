"""The plain reference of ``granite-4.0-h-micro`` (IBM Granite 4.0-H,
``model_type`` ``granitemoehybrid``, dense), with its seeded weights and
its checks.

``logits`` is the forward pass as the published ``config.json`` and the
Hugging Face ``modeling_granitemoehybrid`` equations give it, in
straightforward ``jax.numpy`` and float32 (callers set
``jax.default_matmul_precision("highest")``), with no kernel, cache,
chunk or batching trick, and shares nothing with ``horovod_tpu/models``
but the names of the parameter tree it is handed (``d`` the hidden size):

    h = embedding_multiplier * E[token]
    layer l:  h = h + residual_multiplier * Mixer_l(RMSNorm(h))
              h = h + residual_multiplier * MLP(RMSNorm(h))
              MLP(x) = W_down (silu(W_gate x) * W_up x)      (shared MLP;
              num_local_experts is 0, so there is no routed part)
    logits = (RMSNorm(h) @ E^T) / logits_scaling             (tied head)

``Mixer_l`` where ``layer_types[l] == "attention"``: grouped-query heads
(query head ``h`` reads key-value head ``h // group``), **no positional
term** (``position_embedding_type`` ``nope``),
``softmax(attention_multiplier * q k^T + causal mask) v``, a full masked
softmax.  Where it is ``"mamba"`` (Mamba-2, H heads of P channels, state
N, one group): ``[z, xBC, dt] = W_in x``; ``xBC = silu(conv(xBC))``, a
causal depthwise convolution of width ``mamba_d_conv`` with bias;
``[x, B, C] = xBC``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``;

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t
    y_t[h] = S_t[h] C_t + D[h] x_t[h]

as a ``lax.scan`` over positions, one token at a time;
``y = RMSNorm(y * silu(z))`` (the gate before the norm, one group);
``W_out y``.  The only departure from the published model is the weights:
seeded random ones (``weights``), since nothing can be downloaded here.

``weights`` makes the tree the replica is handed: bfloat16, a normal law
of variance one over the fan-in for the linear maps (as
``reference.lm_weights``; the tied matrix 1 / (d * embedding_multiplier^2),
see there), and for the recurrence the family's published
initialisation, so that states neither die nor blow up: ``A_log = log(u)``
with ``u`` uniform on 1 to 16, ``dt_bias`` the inverse softplus of a
log-uniform draw on 0.001 to 0.1, ``D = 1``, convolution weights and bias
uniform within +-1/2 (those three vectors a head stay float32).

``served_gap`` is the comparison every run of the cell makes (``serve.py``
says over which requests): the reference over a finished request's
prompt and served tokens, a layer at a time so that one layer's float32
weights are on the device at once; for every served token the gap by
which its logit lies below the reference's best.  Its control is the
same pass with both operands of every linear map rounded to 8 bits
(``reference.int8``), the state left in float32.  ``check`` is
``--check reference``: prefill of a padded bucket into one slot of a
slot cache, then decode through the cache, every row against ``logits``.
"""
from __future__ import annotations

import math

import reference

TOLERANCE = 0.025
KINDS = ("mamba", "attention")


# ------------------------------------------------------------- the equations
def linear(spec, x, x_axes, w, w_axes, operands=None):
    import jax.numpy as jnp
    if operands is not None:
        x, w = operands(x, x_axes), operands(w, w_axes)
    return jnp.einsum(spec, x, w)


def mlp(layer, x, cfg, operands=None):
    import jax
    h = reference.rms_norm(x, layer["mlp_norm"]["scale"],
                           cfg["rms_norm_eps"])
    weights = layer["mlp"]
    gated = jax.nn.silu(linear("btd,df->btf", h, -1,
                               weights["gate"]["kernel"], 0, operands)) \
        * linear("btd,df->btf", h, -1, weights["up"]["kernel"], 0, operands)
    return x + cfg["residual_multiplier"] * linear(
        "btf,fd->btd", gated, -1, weights["down"]["kernel"], 0, operands)


def attention(layer, x, cfg, operands=None):
    import jax
    import jax.numpy as jnp
    h = reference.rms_norm(x, layer["mixer_norm"]["scale"],
                           cfg["rms_norm_eps"])
    attn = layer["attn"]
    q, k, v = (linear("btd,dhk->bthk", h, -1, attn[name]["kernel"], 0,
                      operands) for name in ("wq", "wk", "wv"))
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(each, group, axis=2) for each in (k, v))
    t = x.shape[1]
    scores = cfg["attention_multiplier"] \
        * jnp.einsum("bqhk,bshk->bhqs", q, k)         # no position term
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    mixed = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(scores, -1), v)
    return x + cfg["residual_multiplier"] * linear(
        "bthk,hkd->btd", mixed, (-2, -1), attn["wo"]["kernel"], (0, 1),
        operands)


def mamba(layer, x, cfg, operands=None, state_at=None):
    """The Mamba-2 mixer over whole sequences.  ``state_at`` also returns
    the state after position ``state_at - 1`` (for the tests)."""
    import jax
    import jax.numpy as jnp
    heads, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], \
        cfg["mamba_d_state"]
    inner, width = heads * p, cfg["mamba_d_conv"]
    assert cfg["mamba_n_groups"] == 1
    w = layer["mamba"]
    h = reference.rms_norm(x, layer["mixer_norm"]["scale"],
                           cfg["rms_norm_eps"])
    proj = linear("btd,df->btf", h, -1, w["in_proj"]["kernel"], 0, operands)
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * n], axis=-1)
    t = x.shape[1]
    padded = jnp.pad(xbc, [(0, 0), (width - 1, 0), (0, 0)])
    xbc = jax.nn.silu(sum(padded[:, i:i + t] * w["conv_kernel"][i]
                          for i in range(width)) + w["conv_bias"])
    xs, b, c = jnp.split(xbc, [inner, inner + n], axis=-1)
    xs = xs.reshape(*xs.shape[:2], heads, p)
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # [B, T, H]
    a = -jnp.exp(w["A_log"])

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        y_t = jnp.einsum("bhpn,bn->bhp", state, c_t) \
            + w["D"][:, None] * x_t
        return state, (y_t, state) if state_at is not None else (y_t, ())

    _, (y, states) = jax.lax.scan(
        step, jnp.zeros((x.shape[0], heads, p, n), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (xs, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(*x.shape[:2], inner)
    y = reference.rms_norm(y * jax.nn.silu(z), w["norm"]["scale"],
                           cfg["rms_norm_eps"])
    out = x + cfg["residual_multiplier"] * linear(
        "btf,fd->btd", y, -1, w["out_proj"]["kernel"], 0, operands)
    return out if state_at is None else (out, states[state_at - 1])


def embed(params, tokens, cfg):
    return cfg["embedding_multiplier"] * params["embed"]["embedding"][tokens]


def head(params, x, cfg, operands=None):
    x = reference.rms_norm(x, params["final_norm"]["scale"],
                           cfg["rms_norm_eps"])
    return linear("btd,vd->btv", x, -1, params["embed"]["embedding"], -1,
                  operands) / cfg["logits_scaling"]


def logits(params, tokens, cfg: dict, operands=None):
    """tokens [B, T] -> logits [B, T, vocab], float32."""
    x = embed(params, tokens, cfg)
    for i, kind in enumerate(cfg["layer_types"]):
        layer = params[f"layer_{i}"]
        x = (mamba if kind == "mamba" else attention)(layer, x, cfg,
                                                      operands)
        x = mlp(layer, x, cfg, operands)
    return head(params, x, cfg, operands)


# ---------------------------------------------------------------- the weights
def weights(run):
    """The configuration's weights from the seed, made on the device;
    the tree has the names the program's hybrid decoder gives its
    parameters and nothing else of the program."""
    import jax
    import jax.numpy as jnp

    cfg = run.config
    dtype = run.resolve(cfg["model"]["args"]["param_dtype"][1:])
    d, ff, vocab = cfg["hidden_size"], cfg["shared_intermediate_size"], \
        cfg["vocab_size"]
    q_heads, kv_heads = cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    head_dim = d // q_heads
    heads, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], \
        cfg["mamba_d_state"]
    inner, width = heads * p, cfg["mamba_d_conv"]
    channels = inner + 2 * n
    # (law, shape, fan-in); "ones" and the recurrence's own laws by name.
    normal = lambda fan_in, *shape: ("normal", shape, fan_in)   # noqa: E731
    shared = {"mixer_norm": {"scale": ("ones", (d,), 0)},
              "mlp_norm": {"scale": ("ones", (d,), 0)},
              "mlp": {"gate": {"kernel": normal(d, d, ff)},
                      "up": {"kernel": normal(d, d, ff)},
                      "down": {"kernel": normal(ff, ff, d)}}}
    kinds = {
        "attention": {"attn": {
            "wq": {"kernel": normal(d, d, q_heads, head_dim)},
            "wk": {"kernel": normal(d, d, kv_heads, head_dim)},
            "wv": {"kernel": normal(d, d, kv_heads, head_dim)},
            "wo": {"kernel": normal(d, q_heads, head_dim, d)}}},
        "mamba": {"mamba": {
            "in_proj": {"kernel": normal(d, d, 2 * inner + 2 * n + heads)},
            "out_proj": {"kernel": normal(inner, inner, d)},
            "conv_kernel": ("half", (width, channels), 0),
            "conv_bias": ("half", (channels,), 0),
            "A_log": ("a_log", (heads,), 0),
            "dt_bias": ("dt_bias", (heads,), 0),
            "D": ("ones32", (heads,), 0),
            "norm": {"scale": ("ones", (inner,), 0)}}}}
    # The tied matrix: variance 1 / (d * embedding_multiplier^2), so that
    # the embedded token, embedding_multiplier * E[token], has the
    # variance 1/d that ``reference.lm_weights`` gives an embedding.  At
    # variance 1/d the token's own row would win every arg-max through
    # the tied head (a logit 6 deviations above the others'), every
    # request would repeat its last prompt token, and no rounding could
    # move what is compared.
    outer = {"embed": {"embedding": normal(
                 d * cfg["embedding_multiplier"] ** 2, vocab, d)},
             "final_norm": {"scale": ("ones", (d,), 0)}}

    def draw(key, law, shape, fan_in):
        if law == "ones":
            return jnp.ones(shape, dtype)
        if law == "ones32":
            return jnp.ones(shape, jnp.float32)
        if law == "normal":
            return (fan_in ** -0.5 * jax.random.normal(
                key, shape, jnp.float32)).astype(dtype)
        if law == "half":
            return jax.random.uniform(key, shape, jnp.float32, -0.5,
                                      0.5).astype(dtype)
        if law == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        step = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return step + jnp.log(-jnp.expm1(-step))     # inverse softplus

    def maker(tree):
        """One compiled program for a tree of laws; a layer's is
        compiled once for its kind and called with each layer's key (40
        layers in one program take minutes to compile)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, tuple))
        return jax.jit(lambda key: jax.tree_util.tree_unflatten(treedef, [
            draw(jax.random.fold_in(key, at), *spec)
            for at, (_, spec) in enumerate(flat)]))

    key = jax.random.key(run.seed)
    layer = {kind: maker({**shared, **own}) for kind, own in kinds.items()}
    return {**maker(outer)(jax.random.fold_in(key, 0)),
            **{f"layer_{i}": layer[kind](jax.random.fold_in(key, 1 + i))
               for i, kind in enumerate(cfg["layer_types"])}}


# ------------------------------------------------- what every run compares
def served_gap(cfg: dict, control: bool = False):
    """The comparison of one finished request with the reference, as a
    function of ``(params, tokens, first, length)`` with the meaning and
    the results of ``reference.lm_served_gap``.  A layer at a time: each
    kind of layer is one jitted function that is handed its layer's
    weights and casts them to float32 itself, so the whole model is
    never on the device in float32 (12.8 GB at the published widths)."""
    import jax
    import jax.numpy as jnp

    def full(tree):
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)

    def layer_fn(kind, operands):
        def run_layer(layer, x):
            layer = full(layer)
            with jax.default_matmul_precision("highest"):
                x = (mamba if kind == "mamba" else attention)(
                    layer, x, cfg, operands)
                return mlp(layer, x, cfg, operands)
        return jax.jit(run_layer)

    def head_fn(operands):
        def run_head(outer, x):
            with jax.default_matmul_precision("highest"):
                return head(full(outer), x, cfg, operands)[0]
        return jax.jit(run_head)

    passes = {"gap": None, **({"control_gap": reference.int8}
                              if control else {})}
    layers = {(kind, name): layer_fn(kind, operands)
              for kind in KINDS for name, operands in passes.items()}
    heads = {name: head_fn(operands) for name, operands in passes.items()}

    @jax.jit
    def read(logits, chosen, tokens, first, length):
        at = jnp.arange(tokens.shape[1])
        live = (at >= first - 1) & (at < length - 1)   # t predicts t + 1
        below = jnp.where(live, jnp.max(logits, -1) - jnp.take_along_axis(
            logits, chosen[:, None], -1)[:, 0], 0.0)
        return jnp.max(below), jnp.sum(below)

    def gaps(params, tokens, first, length):
        tokens = jnp.asarray(tokens)
        outer = {"embed": params["embed"],
                 "final_norm": params["final_norm"]}
        seen, reference_logits = {}, None
        for name in passes:
            x = embed(full(outer), tokens, cfg)
            for i, kind in enumerate(cfg["layer_types"]):
                x = layers[kind, name](params[f"layer_{i}"], x)
            logits = heads[name](outer, x)
            if name == "gap":
                reference_logits = logits
                chosen = jnp.roll(tokens[0], -1)       # what was served
            else:
                chosen = jnp.argmax(logits, -1)        # the control's best
            seen[name], seen[name + "_sum"] = read(
                reference_logits, chosen, tokens, first, length)
        return seen

    return gaps


# -------------------------------------------------------- --check reference
def check(run, cfg: dict) -> dict:
    """Prefill one prompt as the executor does (a batch of one, padded to
    its bucket, the true length passed), insert it into a slot of a slot
    cache, then decode through the cache; every row against ``logits``.
    The prompt ends inside the second chunk of the scan, so the state has
    to cross a chunk's edge and stop short of the padding."""
    import jax
    import jax.numpy as jnp

    kinds = ("mamba", "mamba", "attention", "mamba")
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

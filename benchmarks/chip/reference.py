"""The plain reference, and the two dearer checks that use it.

``lm_logits`` is the decoder of the DeepSeek LLM / LLaMA family as
published (arXiv:2401.02954, section 2; the Hugging Face ``modeling_llama``
equations): pre-norm blocks, RMSNorm, rotary positions on rotated halves,
multi-head causal attention, a SiLU-gated MLP, untied output head.  It is
straightforward ``jax.numpy`` in float32 at the highest matmul precision,
with no kernel, cache, batching or bfloat16, and shares nothing with
``horovod_tpu/models`` but the names of the parameter tree it is handed.

    run.py --workload <cell> --check reference
        the function the cell's configuration names under "reference"
        (``"reference:check_serve"``; another architecture brings a
        module of its own beside this one), called with ``(run,
        config)``: the configuration through the program at the
        published widths against its plain reference.  It owns its sizes
        and its tolerance and returns ``error`` (and ``loss_error``,
        ``grad_error`` where it has them) with ``tolerance``.  The two
        here run 2 layers at 256 positions: for a train configuration
        logits, loss and two gradients; for a serve configuration
        prefill into a slot, then 8 rows decoded through the cache,
        against the reference's full forward pass.
    run.py --workload <cell> --check mesh
        one global batch on the cell's mesh and on one device: the first
        losses agree (chip_smoke.gpt_cross_check, at the cell's sizes).

Neither is part of a timed run.  What every serving run does compare,
once its window has closed, is what it served: ``lm_weights`` makes the
weights the replica is handed (the benchmark's own, from the seed), and
``lm_served_gap`` runs the reference once over a finished request's
prompt and served tokens and reads the widest gap by which a served
token's logit lies below the reference's best (``serve.py`` says which
requests; ``PERF.md`` section 2 how the limits were set).  Its control is
the same reference computed in int8 (both operands of every linear map
rounded to 8 bits), the precision below the configuration's bfloat16.
The checks' tolerance is on the largest absolute
difference over the largest absolute reference value; 0.025 admits
bfloat16 compute (8 bits of mantissa through two layers read 0.007 to
0.013 in PR 23) and refuses a lower precision or a missing term, which
move the logits by tenths.
"""
from __future__ import annotations

import gc
import json
import math

TOLERANCE = 0.025
MESH_RTOL = 2e-2


def rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rotary(x, theta):
    """x [B, T, H, D]: rotate the two halves of every head by position."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def int8(x, axes):
    """``x`` rounded to 8 bits, one scale for each slice along ``axes``
    (the axes a matmul sums over): what an int8 matmul is handed."""
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(x), axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def lm_logits(params, tokens, cfg: dict, operands=None):
    """tokens [B, T] -> logits [B, T, vocab], float32.  ``operands``
    (``int8``) rounds both operands of every linear map first: the same
    pass computed in a lower precision, the comparison's control."""
    import jax
    import jax.numpy as jnp

    def linear(spec, x, x_axes, w, w_axes):
        if operands is not None:
            x, w = operands(x, x_axes), operands(w, w_axes)
        return jnp.einsum(spec, x, w)

    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    x = params["embed"]["embedding"][tokens]
    t = tokens.shape[1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(cfg["num_hidden_layers"]):
        layer = params[f"layer_{i}"]
        h = rms_norm(x, layer["attn_norm"]["scale"], eps)
        attn = layer["attn"]
        q, k, v = (linear("btd,dhk->bthk", h, -1, attn[name]["kernel"], 0)
                   for name in ("wq", "wk", "wv"))
        q, k = rotary(q, theta), rotary(k, theta)
        scores = jnp.einsum("bqhk,bshk->bhqs", q, k) \
            / math.sqrt(q.shape[-1])
        scores = jnp.where(causal, scores, -jnp.inf)
        mixed = jnp.einsum("bhqs,bshk->bqhk",
                           jax.nn.softmax(scores, -1), v)
        x = x + linear("bthk,hkd->btd", mixed, (-2, -1),
                       attn["wo"]["kernel"], (0, 1))
        h = rms_norm(x, layer["mlp_norm"]["scale"], eps)
        mlp = layer["mlp"]
        gated = jax.nn.silu(linear("btd,df->btf", h, -1,
                                   mlp["gate"]["kernel"], 0)) \
            * linear("btd,df->btf", h, -1, mlp["up"]["kernel"], 0)
        x = x + linear("btf,fd->btd", gated, -1, mlp["down"]["kernel"], 0)
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    return linear("btd,dv->btv", x, -1, params["lm_head"]["kernel"], 0)


def lm_weights(run):
    """The configuration's weights from the seed, in the type it serves,
    made on the device in one jitted call: a normal law of variance one
    over the fan-in for a matrix, ones for a norm.  The tree has the
    names the program's decoder gives its parameters and nothing else of
    the program."""
    import jax
    import jax.numpy as jnp

    cfg = run.config
    dtype = run.resolve(cfg["model"]["args"]["param_dtype"][1:])
    d, ff, vocab = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["vocab_size"]
    heads = cfg["num_attention_heads"]
    head = d // heads
    matrix = lambda *shape: {"kernel": shape}              # noqa: E731
    layer = {"attn_norm": {"scale": None}, "mlp_norm": {"scale": None},
             "attn": {"wq": matrix(d, heads, head),
                      "wk": matrix(d, heads, head),
                      "wv": matrix(d, heads, head),
                      "wo": matrix(heads, head, d)},
             "mlp": {"gate": matrix(d, ff), "up": matrix(d, ff),
                     "down": matrix(ff, d)}}
    tree = {"embed": {"embedding": (vocab, d)},
            "final_norm": {"scale": None}, "lm_head": matrix(d, vocab),
            **{f"layer_{i}": layer
               for i in range(cfg["num_hidden_layers"])}}
    fan_in = {"wo": heads * head, "down": ff}

    def make(key):
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: x is None or isinstance(x, tuple))
        leaves = []
        for at, (path, shape) in enumerate(flat):
            if shape is None:
                leaves.append(jnp.ones((d,), dtype))
                continue
            std = fan_in.get(path[-2].key, d) ** -0.5
            leaves.append((std * jax.random.normal(
                jax.random.fold_in(key, at), shape, jnp.float32)
            ).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)(jax.random.key(run.seed))


def lm_served_gap(cfg: dict, control: bool = False):
    """The comparison of one finished request with the reference, as a
    jitted function of ``(params, tokens, first, length)``.  ``tokens``
    [1, T] is the request's prompt, then what the replica served, then
    padding (causal, so the padding touches nothing before it); the
    served tokens are ``tokens[0, first:length]``.  For every served
    token it reads the gap by which its logit lies below the reference's
    best at its position, and returns the widest, ``gap``, and their
    sum, ``gap_sum``; with ``control`` also ``control_gap`` and
    ``control_gap_sum``: the same for the token that the reference
    computed in int8 puts first."""
    import jax
    import jax.numpy as jnp

    def gaps(params, tokens, first, length):
        full = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), params)
        at = jnp.arange(tokens.shape[1])
        live = (at >= first - 1) & (at < length - 1)   # t predicts t + 1
        with jax.default_matmul_precision("highest"):
            logits = lm_logits(full, tokens, cfg)[0]
            best = jnp.max(logits, -1)

            def read(chosen, name):
                below = jnp.where(live, best - jnp.take_along_axis(
                    logits, chosen[:, None], -1)[:, 0], 0.0)
                return {name: jnp.max(below), name + "_sum": jnp.sum(below)}

            seen = read(jnp.roll(tokens[0], -1), "gap")
            if control:
                seen.update(read(jnp.argmax(lm_logits(
                    full, tokens, cfg, operands=int8)[0], -1),
                    "control_gap"))
        return seen

    return jax.jit(gaps)


def lm_loss(params, tokens, labels, cfg: dict):
    """Mean next-token cross entropy."""
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(lm_logits(params, tokens, cfg), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def error(got, want) -> float:
    import jax.numpy as jnp
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(jnp.asarray(got, jnp.float32) - want))
                 / jnp.max(jnp.abs(want)))


def check_train(run, cfg: dict) -> dict:
    import jax
    from horovod_tpu import training

    layers, positions = 2, 256
    cfg = {**cfg, "num_hidden_layers": layers}
    model = run.build_model(num_layers=layers)
    tokens = jax.random.randint(jax.random.key(run.seed),
                                (2, positions + 1), 0, cfg["vocab_size"])
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    params = jax.jit(model.init)(jax.random.key(run.seed), inputs)["params"]

    def program(p):
        logits = model.apply({"params": p}, inputs, train=True)
        return training.cross_entropy_loss(logits, labels), logits

    (loss, logits), grads = jax.jit(
        jax.value_and_grad(program, has_aux=True))(params)
    with jax.default_matmul_precision("highest"):
        want_logits = jax.jit(lambda p: lm_logits(p, inputs, cfg))(params)
        want_loss, want_grads = jax.jit(jax.value_and_grad(
            lambda p: lm_loss(p, inputs, labels, cfg)))(params)
    pick = lambda g: (g["lm_head"]["kernel"],            # noqa: E731
                      g["layer_0"]["attn"]["wq"]["kernel"])
    return {"compared": f"logits {tuple(logits.shape)}, loss, and the "
                        "gradients of lm_head and layer_0.attn.wq",
            "layers": layers, "positions": positions,
            "tolerance": TOLERANCE, "error": error(logits, want_logits),
            "loss": float(loss), "reference_loss": float(want_loss),
            "loss_error": abs(float(loss) - float(want_loss))
            / abs(float(want_loss)),
            "grad_error": max(error(a, b) for a, b in
                              zip(pick(grads), pick(want_grads)))}


def check_serve(run, cfg: dict) -> dict:
    """Prefill one prompt as the executor does (batch of one, padded to
    its bucket), insert it into a slot of the slot cache, then decode
    through the cache; every row against the full forward pass."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import transformer as tfm

    layers, positions, prompt, decoded = 2, 256, 248, 8
    slots, slot = 4, 3
    cfg = {**cfg, "num_hidden_layers": layers}
    model = tfm.TransformerLM(run.model_config(
        num_layers=layers, decode=True, max_seq_len=positions))
    tokens = jax.random.randint(jax.random.key(run.seed), (1, positions),
                                2, cfg["vocab_size"])
    params = jax.jit(model.init)(jax.random.key(run.seed),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    padded = tokens.at[:, prompt:].set(0)           # the bucket's padding
    logits, cache1 = jax.jit(lambda p, t: tfm.prefill(
        model, {"params": p}, t, lengths=prompt))(params, padded)
    rows = [logits[0, prompt - 1]]
    _, empty = jax.jit(lambda p: model.apply(
        {"params": p}, jnp.zeros((slots, 1), jnp.int32),
        mutable=["cache"]))(params)
    cache = jax.tree_util.tree_map(
        lambda big, small: big.at[slot].set(small[0]),
        tfm._with_cache_index(empty["cache"], 0), cache1)
    decode = jax.jit(lambda p, c, t: tfm.decode_step(
        model, {"params": p}, c, t))
    for at in range(prompt, prompt + decoded):
        fed = jnp.zeros((slots, 1), jnp.int32).at[slot, 0].set(
            tokens[0, at])
        logits, cache = decode(params, cache, fed)
        rows.append(logits[slot, 0])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: lm_logits(p, tokens, cfg))(
            jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params))
    return {"compared": f"{len(rows)} logit rows (prefill of {prompt} "
                        f"tokens into slot {slot}, then {decoded} decoded "
                        f"through the cache) x {cfg['vocab_size']}",
            "layers": layers, "positions": positions,
            "tolerance": TOLERANCE,
            "error": error(jnp.stack(rows),
                           want[0, prompt - 1:prompt + decoded])}


def check_mesh(run) -> dict:
    """chip_smoke.gpt_cross_check at the cell's own sizes: the traffic's
    per-chip batch as ONE global batch, on all the cell's chips and on
    one; a missing or partial reduction moves the losses apart."""
    import jax
    import numpy as np

    import train

    losses = {}
    for name, devices in (("mesh", run.devices),
                          ("one_device", run.devices[:1])):
        trainer = train.build_trainer(run, devices)
        batch = train.make_batch(run, trainer.mesh, trainer.batch_spec,
                                 run.traffic["batch_per_chip"])
        state = trainer.init(jax.random.key(run.seed), batch)
        seen = []
        for _ in range(3):
            state, metrics = trainer.step(state, batch)
            seen.append(float(np.asarray(metrics["loss"])))
        losses[name] = seen
        del state, batch, trainer
        gc.collect()
    rel = [abs(a - b) / abs(b)
           for a, b in zip(losses["mesh"], losses["one_device"])]
    return {"compared": f"3 losses on a global batch of "
                        f"{run.traffic['batch_per_chip']}, {len(run.devices)}"
                        " devices against 1", "losses": losses,
            "error": max(rel), "tolerance": MESH_RTOL}


def check(run, which: str) -> int:
    """Run one check, print its one JSON line, exit 0 only if it holds."""
    cfg = run.config
    seen = check_mesh(run) if which == "mesh" \
        else run.resolve(cfg["reference"])(run, cfg)
    worst = max(seen["error"], seen.get("grad_error", 0.0),
                seen.get("loss_error", 0.0))
    seen.update(config=cfg["name"], device=run.devices[0].device_kind,
                ok=bool(worst <= seen["tolerance"]))
    run.say("check " + json.dumps(seen))
    return 0 if seen["ok"] else 1

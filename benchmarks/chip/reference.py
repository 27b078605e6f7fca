"""The plain reference, and the two dearer checks that use it.

``lm_logits`` is the decoder of the DeepSeek LLM / LLaMA family as
published (arXiv:2401.02954, section 2; the Hugging Face ``modeling_llama``
equations): pre-norm blocks, RMSNorm, rotary positions on rotated halves,
multi-head causal attention, a SiLU-gated MLP, untied output head.  It is
straightforward ``jax.numpy`` in float32 at the highest matmul precision,
with no kernel, cache, batching or bfloat16, and shares nothing with
``horovod_tpu/models`` but the names of the parameter tree it is handed.

    run.py --workload <cell> --check reference
        the cell's configuration through the program at the published
        widths (2 layers, 256 positions) against the reference: for a
        train configuration logits, loss and two gradients; for a serve
        configuration prefill into a slot, then 8 rows decoded through
        the cache, against the reference's full forward pass.
    run.py --workload <cell> --check mesh
        one global batch on the cell's mesh and on one device: the first
        losses agree (chip_smoke.gpt_cross_check, at the cell's sizes).

Neither is part of a timed run.  The tolerance is on the largest absolute
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
LAYERS, POSITIONS = 2, 256
PROMPT, DECODED = 248, 8


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


def lm_logits(params, tokens, cfg: dict):
    """tokens [B, T] -> logits [B, T, vocab], float32."""
    import jax
    import jax.numpy as jnp

    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    x = params["embed"]["embedding"][tokens]
    t = tokens.shape[1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(cfg["num_hidden_layers"]):
        layer = params[f"layer_{i}"]
        h = rms_norm(x, layer["attn_norm"]["scale"], eps)
        attn = layer["attn"]
        q = rotary(jnp.einsum("btd,dhk->bthk", h, attn["wq"]["kernel"]),
                   theta)
        k = rotary(jnp.einsum("btd,dhk->bthk", h, attn["wk"]["kernel"]),
                   theta)
        v = jnp.einsum("btd,dhk->bthk", h, attn["wv"]["kernel"])
        scores = jnp.einsum("bqhk,bshk->bhqs", q, k) \
            / math.sqrt(q.shape[-1])
        scores = jnp.where(causal, scores, -jnp.inf)
        mixed = jnp.einsum("bhqs,bshk->bqhk",
                           jax.nn.softmax(scores, -1), v)
        x = x + jnp.einsum("bthk,hkd->btd", mixed, attn["wo"]["kernel"])
        h = rms_norm(x, layer["mlp_norm"]["scale"], eps)
        mlp = layer["mlp"]
        x = x + (jax.nn.silu(h @ mlp["gate"]["kernel"])
                 * (h @ mlp["up"]["kernel"])) @ mlp["down"]["kernel"]
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    return x @ params["lm_head"]["kernel"]


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

    model = run.build_model(num_layers=LAYERS)
    tokens = jax.random.randint(jax.random.key(run.seed),
                                (2, POSITIONS + 1), 0, cfg["vocab_size"])
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
            "error": error(logits, want_logits),
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

    slots, slot = 4, 3
    model = tfm.TransformerLM(run.model_config(
        num_layers=LAYERS, decode=True, max_seq_len=POSITIONS))
    tokens = jax.random.randint(jax.random.key(run.seed), (1, POSITIONS),
                                2, cfg["vocab_size"])
    params = jax.jit(model.init)(jax.random.key(run.seed),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    padded = tokens.at[:, PROMPT:].set(0)           # the bucket's padding
    logits, cache1 = jax.jit(lambda p, t: tfm.prefill(
        model, {"params": p}, t, lengths=PROMPT))(params, padded)
    rows = [logits[0, PROMPT - 1]]
    _, empty = jax.jit(lambda p: model.apply(
        {"params": p}, jnp.zeros((slots, 1), jnp.int32),
        mutable=["cache"]))(params)
    cache = jax.tree_util.tree_map(
        lambda big, small: big.at[slot].set(small[0]),
        tfm._with_cache_index(empty["cache"], 0), cache1)
    decode = jax.jit(lambda p, c, t: tfm.decode_step(
        model, {"params": p}, c, t))
    for at in range(PROMPT, PROMPT + DECODED):
        fed = jnp.zeros((slots, 1), jnp.int32).at[slot, 0].set(
            tokens[0, at])
        logits, cache = decode(params, cache, fed)
        rows.append(logits[slot, 0])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: lm_logits(p, tokens, cfg))(
            jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params))
    return {"compared": f"{len(rows)} logit rows (prefill of {PROMPT} "
                        f"tokens into slot {slot}, then {DECODED} decoded "
                        f"through the cache) x {cfg['vocab_size']}",
            "error": error(jnp.stack(rows),
                           want[0, PROMPT - 1:PROMPT + DECODED])}


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
    if which == "mesh":
        seen = check_mesh(run)
    else:
        cfg = {**cfg, "num_hidden_layers": LAYERS}
        seen = (check_serve if cfg["driver"] == "serve"
                else check_train)(run, cfg)
        seen.update(layers=LAYERS, positions=POSITIONS, tolerance=TOLERANCE)
    worst = max(seen["error"], seen.get("grad_error", 0.0),
                seen.get("loss_error", 0.0))
    seen.update(config=cfg["name"], device=run.devices[0].device_kind,
                ok=bool(worst <= seen["tolerance"]))
    run.say("check " + json.dumps(seen))
    return 0 if seen["ok"] else 1

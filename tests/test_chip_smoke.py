"""The chip check's CPU half: chip_smoke.py's control flow, the no-TPU
exits, the compile-cache placement, and the flash kernels compiled for a
v5e without one (libtpu's compile-only topology).

Every subprocess this file needs starts together in one fixture and the
dry run is awaited last, so the file costs its slowest child, not the sum
of the children and the in-process compiles.
"""
from __future__ import annotations

import functools
import importlib
import math
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MOSAIC = 'custom_call_target="tpu_custom_call"'
_CACHE_PROBE = ("from horovod_tpu.common.compile_cache import "
                "configure_compile_cache as c; import jax; "
                "print(c()); print(jax.config.jax_compilation_cache_dir)")

fa = importlib.import_module("horovod_tpu.ops.flash_attention")


def _git_status() -> str | None:
    """What git sees outside .gitignore; None where the checkout is not a
    repository."""
    out = subprocess.run(["git", "status", "--porcelain"], cwd=REPO,
                         capture_output=True, text=True)
    return out.stdout if out.returncode == 0 else None


class _Children:
    """Subprocesses started together; ``result(name)`` waits for one."""

    def __init__(self) -> None:
        base = {**os.environ, "JAX_PLATFORMS": "cpu"}
        no_cache = {k: v for k, v in base.items()
                    if k != "JAX_COMPILATION_CACHE_DIR"}
        self.tree_before = _git_status()
        self.started = time.monotonic()
        self._done: dict[str, tuple[int, str, str]] = {}
        self._procs = {
            "smoke_no_tpu": self._spawn(["chip_smoke.py"], base),
            "cache_a": self._spawn(["-c", _CACHE_PROBE], no_cache),
            "cache_b": self._spawn(["-c", _CACHE_PROBE], no_cache),
            # Persist even its sub-second compiles: a warm suite cache
            # then halves the slowest child.
            "dry_run": self._spawn(
                ["chip_smoke.py", "--dry-run-cpu"],
                {**base,
                 "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}),
        }

    @staticmethod
    def _spawn(argv: list[str], env: dict) -> subprocess.Popen:
        return subprocess.Popen([sys.executable, *argv], cwd=REPO, env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True)

    def result(self, name: str, timeout: float) -> tuple[int, str, str]:
        if name not in self._done:
            proc = self._procs[name]
            out, err = proc.communicate(timeout=timeout)
            self._done[name] = (proc.returncode, out, err)
        return self._done[name]

    def close(self) -> None:
        for proc in self._procs.values():
            if proc.poll() is None:
                proc.kill()
            proc.communicate()


@pytest.fixture
def hvd_log(caplog):
    """The repo logger does not propagate to caplog's root handler."""
    from horovod_tpu.common.logging import logger
    logger.addHandler(caplog.handler)
    try:
        yield caplog
    finally:
        logger.removeHandler(caplog.handler)


@pytest.fixture(scope="module")
def children():
    kids = _Children()
    try:
        yield kids
    finally:
        kids.close()


@pytest.mark.parametrize("name", ["smoke_no_tpu"])
def test_no_tpu_is_a_fast_named_failure(children, name):
    """Without a TPU the smoke does not fall back to the CPU: non-zero exit
    within 10 s, one line naming the missing TPU, no result line."""
    rc, out, err = children.result(name, timeout=30)
    elapsed = time.monotonic() - children.started
    assert rc != 0
    assert "no TPU" in err and "'cpu'" in err, err[-500:]
    assert out.strip() == "", out
    assert elapsed < 10.0, elapsed


def test_cache_helper_leaves_a_set_variable_alone(monkeypatch, tmp_path):
    from horovod_tpu.common.compile_cache import configure_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_default_is_one_fixed_place_in_the_checkout(children):
    """Unset, the cache is <checkout>/.jax_cache in every process: no
    tempfile, pid or clock in the path, so a second process hits."""
    want = os.path.join(REPO, ".jax_cache")
    for name in ("cache_a", "cache_b"):
        rc, out, err = children.result(name, timeout=60)
        assert rc == 0, err[-2000:]
        assert out.split() == [want, want], out


@pytest.fixture(scope="module")
def v5e():
    """One device of libtpu's compile-only v5e:2x2 topology: lowers and
    compiles (Mosaic included) for the chip, runs nothing.  The
    persistent compile cache is off meanwhile: without a chip no process
    here can load what such a compile would leave in it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as exc:  # noqa: BLE001 - any libtpu load failure
        pytest.skip(f"compile-only v5e:2x2 topology unavailable: "
                    f"{type(exc).__name__}: {exc}")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)),
                            PartitionSpec())
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


def _compile_attention_grad(v5e, monkeypatch, shape, **blocks) -> str:
    # The public entry point picks the kernels by the DEFAULT backend,
    # which is the CPU here; the compile target is the v5e.
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, **blocks)
        return out.astype(jnp.float32).sum()

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return grad.lower(qkv, qkv, qkv).compile().as_text()


def test_flash_kernels_compile_for_v5e_at_smoke_shapes(v5e, monkeypatch):
    """Forward, dq and dk/dv kernels at chip_smoke's GPT-small shapes
    (batch 8, seq 2048, 12 heads of 64, bf16, blocks 1024/1024) fit the
    v5e's scoped VMEM and lower through Mosaic."""
    text = _compile_attention_grad(v5e, monkeypatch, (8, 2048, 12, 64),
                                   block_q=1024, block_k=1024)
    assert text.count(MOSAIC) == 3


def test_flash_odd_length_compiles_for_v5e(v5e, monkeypatch):
    """T=2000 used to degrade to a 125-row block, which the TPU lowering
    refuses while the CPU path passes; the fitted block (80) compiles."""
    text = _compile_attention_grad(v5e, monkeypatch, (1, 2000, 1, 64))
    assert text.count(MOSAIC) == 3


def test_ssm_update_compiles_for_v5e_at_published_shapes(v5e):
    """hvd.ssm_update at granite-4.0-h-micro's decode shapes (32 slots,
    64 heads of 64, state 128: a stored state of [32, 32, 128, 128]
    float32, blocks of 16 rows) lowers through Mosaic, fits the v5e's
    scoped VMEM, and writes the donated state in place."""
    from horovod_tpu.ops import ssm

    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e)

    slots, heads, p, n = 32, 64, 64, 128
    assert ssm.state_shape(slots, heads, p, n) == (32, 32, 128, 128)
    update = jax.jit(lambda *operands: ssm._ssm_update_pallas(
        *operands, block_groups=16, interpret=False), donate_argnums=0)
    compiled = update.lower(
        shaped(*ssm.state_shape(slots, heads, p, n)), shaped(slots, heads, p),
        shaped(slots, heads), shaped(heads), shaped(slots, n),
        shaped(slots, n), shaped(heads)).compile()
    assert compiled.as_text().count(MOSAIC) == 1
    assert compiled.memory_analysis().alias_size_in_bytes \
        == slots * heads * p * n * 4


def test_kda_update_compiles_for_v5e_at_published_shapes(v5e):
    """hvd.kda_update at Solar-Open2-250B's decode shapes (80 slots, 64
    heads of 128 x 128: a state of [80, 64, 128, 128] float32, blocks of
    16 heads) lowers through Mosaic, fits the v5e's scoped VMEM, and
    writes the donated state in place."""
    from horovod_tpu.ops import kda

    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e)

    slots, heads, d = 80, 64, 128
    update = jax.jit(lambda *operands: kda._kda_update_pallas(
        *operands, block_heads=16, interpret=False), donate_argnums=0)
    small = shaped(slots, heads, d)
    compiled = update.lower(shaped(slots, heads, d, d), small, small, small,
                            small, shaped(slots, heads)).compile()
    assert compiled.as_text().count(MOSAIC) == 1
    assert "hvd.kda_update" in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes \
        == slots * heads * d * d * 4


@pytest.mark.parametrize("tokens", [80, 512])
def test_moe_experts_compiles_for_v5e_at_published_shapes(v5e, tokens):
    """hvd.moe_experts at Solar-Open2-250B's shapes (40 experts held of
    4096 x 1280, top-8 of 320; a decode step of 80 tokens in tiles of 16
    rows, a prefill of 512 in tiles of 32) lowers through Mosaic with
    three blocks of weights held twice over in the v5e's VMEM."""
    from horovod_tpu.models import moe

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    d, ff, held = 4096, 1280, 40
    tile = moe.tile_rows(tokens, 8, 320)
    rows = -(-(tokens * 8 + held * (tile - 1)) // tile) * tile
    products = jax.jit(lambda *operands: moe._experts_pallas(
        *operands, tile=tile, block=moe.hidden_block(ff), interpret=False))
    compiled = products.lower(
        shaped((rows, d)), shaped((rows // tile,), jnp.int32),
        shaped((1,), jnp.int32), shaped((held, d, ff)),
        shaped((held, d, ff)), shaped((held, ff, d))).compile()
    assert compiled.as_text().count(MOSAIC) == 1
    assert "hvd.moe_experts" in compiled.as_text()


def _placed(tree, sharding):
    """``tree``'s shapes on the described device."""
    return jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=sharding), tree)


def _loops_over(text: str, *leaves: str) -> int:
    """``while`` loops of a compiled program that carry one of ``leaves``
    (a shape as HLO prints it): a serial write into such a leaf, a slot
    an iteration."""
    return sum(" while(" in line and any(leaf in line for leaf in leaves)
               for line in text.splitlines())


def _nbytes(tree) -> int:
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def test_the_7b_decode_program_attends_through_the_kernel_on_v5e(
        v5e, monkeypatch):
    """``lm7b_serve_chat_sat``'s decode program (deepseek-llm-7b's widths,
    4 layers, 16 slots of 4,096 positions, bfloat16) compiled for the
    v5e: one hvd.decode_attend custom call a layer, taking the cache's
    leaves as they lie and returning them with the step's row written
    (ISSUE 39: no ``while`` loop over the slots is left in the program),
    so the program still updates the whole cache in place and holds no
    copy of a leaf among its temporaries."""
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.ops import decode_attention as da
    from horovod_tpu.serving import ServeConfig, slotcache
    from horovod_tpu.serving.replica import _decode_model_cfg

    monkeypatch.setattr(da, "_on_tpu", lambda: True)   # the target, not the CPU
    layers, slots, max_seq = 4, 16, 4096
    cfg = ServeConfig.from_env(
        max_batch=slots, max_seq=max_seq, token_budget=1040, paged=False,
        model_cfg=tfm.TransformerConfig(
            vocab_size=102400, num_layers=layers, num_heads=32, d_model=4096,
            d_ff=11008, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
    model = tfm.TransformerLM(_decode_model_cfg(cfg))
    cache = slotcache.DenseSlotCache(cfg, tfm.FAMILY, model, {})

    placed = functools.partial(_placed, sharding=v5e)
    params = placed(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))
    tree = placed(jax.eval_shape(cache._init_cache_impl, params))
    leaf = 2 * slots * max_seq * 32 * 128            # one key leaf, bytes
    cache_bytes = _nbytes(tree)
    assert cache_bytes == layers * (2 * leaf + slots * 4)
    # The last step's result, the host's tokens and where they win.
    compiled = cache._decode_jit.lower(
        params, tree, *placed((jnp.zeros(slots, jnp.int32),
                               jnp.zeros(slots, jnp.int32),
                               jnp.zeros(slots, bool)))).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if MOSAIC in line]
    assert len(calls) == layers
    assert all("hvd.decode_attend" in call for call in calls)
    # Two operands, and two results that alias them.
    assert all(call.count("bf16[16,4096,32,128]") == 4 for call in calls)
    assert " while(" not in text
    memory = compiled.memory_analysis()
    # (each layer's 64 bytes of write cursors are a 512-byte tile there)
    assert memory.alias_size_in_bytes == cache_bytes + layers * (512 - 64)
    assert memory.temp_size_in_bytes < leaf // 4


def test_the_mimo_programs_fit_a_v5e_and_attend_through_the_kernel(
        v5e, monkeypatch):
    """``mimov25_serve_mixlen_sat``'s programs (MiMo-V2.5's widths, 7
    layers, 64 slots, bfloat16) compiled for the v5e.  The decode
    program: one hvd.decode_attend custom call a global layer, taking
    the two leaves with their 4 heads in the lanes as they lie (768 and
    512 wide, 12,288 positions), one hvd.window_attend a window layer
    over its ring's 8 heads in the lanes (1,536 and 1,024 wide, 128
    positions: the same kernel under the rings' own name), one
    hvd.moe_experts a layer that has experts; it updates the whole cache
    in place.  The prefill of 8,192 tokens attends in blocks: its
    temporaries fit beside the weights and the cache."""
    from horovod_tpu.models import hybrid, moe
    from horovod_tpu.ops import decode_attention as da
    from horovod_tpu.serving import ServeConfig, slotcache
    from horovod_tpu.serving.replica import _decode_model_cfg

    sys.path.insert(0, os.path.join(REPO, "benchmarks", "chip"))
    import run as harness
    for module in (da, moe):                # the target, not the CPU
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    file = harness.load_json(harness.HERE, "configs", "MiMo-V2.5.serve.json")
    serve = {**file["serve"],
             "warmup_buckets": tuple(file["serve"]["warmup_buckets"])}
    cfg = ServeConfig(model_cfg=hybrid.HybridConfig(
        **harness.build_args(file)), **serve)
    slots, max_seq = cfg.slots, cfg.max_seq
    assert (slots, max_seq) == (64, 12288)
    model = hybrid.HybridLM(_decode_model_cfg(cfg))
    cache = slotcache.DenseSlotCache(cfg, cfg.model_cfg.family, model, {})

    placed = functools.partial(_placed, sharding=v5e)
    params = placed(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))
    tree = placed(jax.eval_shape(cache._init_cache_impl, params))
    rings = 5 * slots * 128 * 8 * (192 + 128) * 2
    whole = 2 * slots * max_seq * 4 * (192 + 128) * 2
    assert _nbytes(tree) == rings + whole + 7 * slots * 4
    assert 6.85e9 < _nbytes(params) < 6.87e9
    assert tree["layer_0"]["attn"]["cached_key"].shape \
        == (slots, max_seq, 4 * 192)
    assert tree["layer_1"]["attn"]["ring_key"].shape \
        == (slots, 128, 8 * 192)
    assert tree["layer_1"]["attn"]["ring_value"].shape \
        == (slots, 128, 8 * 128)
    compiled = cache._decode_jit.lower(
        params, tree, *placed((jnp.zeros(slots + 4, jnp.int32),
                               jnp.zeros(slots, jnp.int32),
                               jnp.zeros(slots, bool)))).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if MOSAIC in line]
    attend = [call for call in calls if "hvd.decode_attend" in call]
    rings = [call for call in calls if "hvd.window_attend" in call]
    assert len(attend) == 2 and len(rings) == 5 and len(calls) == 2 + 5 + 6
    # The kernels write the step's row: none of the fourteen leaves is
    # written in a loop over the slots (ISSUE 39).
    assert not _loops_over(text, "bf16[64,12288,", "bf16[64,128,")
    assert sum("hvd.moe_experts" in call for call in calls) == 6
    assert all("bf16[64,12288,768]" in call and "bf16[64,12288,512]" in call
               for call in attend)
    assert all("bf16[64,128,1536]" in call and "bf16[64,128,1024]" in call
               for call in rings)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= _nbytes(tree)
    assert memory.temp_size_in_bytes < 0.1e9
    prefill = cache._prefill_jit.lower(
        params, placed(jnp.zeros((1, 8192), jnp.int32)),
        placed(jnp.zeros((), jnp.int32))).compile().memory_analysis()
    # 16 GiB less what the runtime keeps: 15.75 GB usable.
    assert _nbytes(params) + _nbytes(tree) + prefill.temp_size_in_bytes \
        + prefill.output_size_in_bytes < 14.5e9


def test_the_granite_decode_program_attends_through_the_kernel_on_v5e(
        v5e, monkeypatch):
    """``granite4hm_serve_agent_sat``'s decode program (granite-4.0-h-micro
    whole: 36 Mamba-2 and 4 attention layers, 32 slots of 2,560
    positions, bfloat16) compiled for the v5e: each attention layer's 8
    key-value heads of 64 lie in the lanes, ``[32, 2560, 512]``, and one
    hvd.decode_attend custom call a layer takes the two leaves as they
    lie and writes the step's row, beside 36 hvd.ssm_update; no ``while``
    loop over the slots is left, the cache is as large as before and the
    program updates it in place."""
    from horovod_tpu.models import hybrid
    from horovod_tpu.ops import decode_attention as da
    from horovod_tpu.ops import ssm
    from horovod_tpu.serving import ServeConfig, slotcache
    from horovod_tpu.serving.replica import _decode_model_cfg

    sys.path.insert(0, os.path.join(REPO, "benchmarks", "chip"))
    import run as harness
    for module in (da, ssm):                 # the target, not the CPU
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    file = harness.load_json(harness.HERE, "configs",
                             "granite-4.0-h-micro.serve.json")
    serve = {**file["serve"],
             "warmup_buckets": tuple(file["serve"]["warmup_buckets"])}
    cfg = ServeConfig(model_cfg=hybrid.HybridConfig(
        **harness.build_args(file)), **serve)
    slots, max_seq = cfg.slots, cfg.max_seq
    assert (slots, max_seq) == (32, 2560)
    model = hybrid.HybridLM(_decode_model_cfg(cfg))
    cache = slotcache.DenseSlotCache(cfg, cfg.model_cfg.family, model, {})

    placed = functools.partial(_placed, sharding=v5e)
    params = placed(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))
    tree = placed(jax.eval_shape(cache._init_cache_impl, params))
    attn = tree["layer_5"]["attn"]
    assert attn["cached_key"].shape == attn["cached_value"].shape \
        == (slots, max_seq, 8 * 64)
    assert da.kernel_block(attn["cached_key"].shape, jnp.bfloat16,
                           values=attn["cached_value"].shape) == 512
    cache_bytes = _nbytes(tree)
    assert cache_bytes == 3_117_089_280              # as the parent's
    compiled = cache._decode_jit.lower(
        params, tree, *placed((jnp.zeros(slots, jnp.int32),
                               jnp.zeros(slots, jnp.int32),
                               jnp.zeros(slots, bool)))).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if MOSAIC in line]
    attend = [call for call in calls if "hvd.decode_attend" in call]
    # Two operands, and two results that alias them.
    assert len(attend) == 4
    assert all(call.count("bf16[32,2560,512]") == 4 for call in attend)
    assert sum("hvd.ssm_update" in call for call in calls) == 36
    assert len(calls) == 4 + 36
    assert " while(" not in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= cache_bytes
    assert memory.temp_size_in_bytes < 0.1e9


def test_the_solar_decode_program_attends_through_the_kernel_on_v5e(
        v5e, monkeypatch):
    """``solaropen2_serve_reason_sat``'s decode program (Solar-Open2's
    widths, one period, 80 slots of 4,608 positions, bfloat16) compiled
    for the v5e: the attention layer's 8 key-value heads of 128 lie in
    the lanes, ``[80, 4608, 1024]``, and one hvd.decode_attend custom
    call takes the two leaves as they lie, beside three hvd.kda_update
    and four hvd.moe_experts; the program updates the whole cache in
    place and holds no copy of a leaf among its temporaries."""
    from horovod_tpu.models import hybrid, moe
    from horovod_tpu.ops import decode_attention as da
    from horovod_tpu.ops import kda
    from horovod_tpu.serving import ServeConfig, slotcache
    from horovod_tpu.serving.replica import _decode_model_cfg

    sys.path.insert(0, os.path.join(REPO, "benchmarks", "chip"))
    import run as harness
    for module in (da, moe, kda):           # the target, not the CPU
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    file = harness.load_json(harness.HERE, "configs",
                             "Solar-Open2-250B.serve.json")
    serve = {**file["serve"],
             "warmup_buckets": tuple(file["serve"]["warmup_buckets"])}
    cfg = ServeConfig(model_cfg=hybrid.HybridConfig(
        **harness.build_args(file)), **serve)
    slots, max_seq = cfg.slots, cfg.max_seq
    assert (slots, max_seq) == (80, 4608)
    model = hybrid.HybridLM(_decode_model_cfg(cfg))
    cache = slotcache.DenseSlotCache(cfg, cfg.model_cfg.family, model, {})

    placed = functools.partial(_placed, sharding=v5e)
    params = placed(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))
    tree = placed(jax.eval_shape(cache._init_cache_impl, params))
    leaf = slots * max_seq * 8 * 128 * 2             # one key leaf, bytes
    attn = tree["layer_0"]["attn"]
    assert attn["cached_key"].shape == attn["cached_value"].shape \
        == (slots, max_seq, 8 * 128)
    cache_bytes = _nbytes(tree)
    assert cache_bytes == 2_551_972_160              # as the parent's
    assert da.kernel_block(attn["cached_key"].shape, jnp.bfloat16,
                           values=attn["cached_value"].shape) == 512
    compiled = cache._decode_jit.lower(
        params, tree, *placed((jnp.zeros(slots + 4, jnp.int32),
                               jnp.zeros(slots, jnp.int32),
                               jnp.zeros(slots, bool)))).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if MOSAIC in line]
    attend, = [call for call in calls if "hvd.decode_attend" in call]
    # Two operands, and two results that alias them: the kernel writes
    # the step's row, and the loops over the slots that are left write
    # the three KDA layers' convolution windows (ISSUE 39).
    assert attend.count("bf16[80,4608,1024]") == 4
    assert not _loops_over(text, "bf16[80,4608,")
    assert _loops_over(text, "bf16[80,4,24576]") == 3
    assert sum("hvd.kda_update" in call for call in calls) == 3
    assert sum("hvd.moe_experts" in call for call in calls) == 4
    assert len(calls) == 1 + 3 + 4
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= cache_bytes
    assert memory.temp_size_in_bytes < leaf // 4


def test_the_axk1_programs_fit_a_v5e_and_attend_through_the_kernel(
        v5e, monkeypatch):
    """``axk1_serve_longdoc_sat``'s programs (A.X-K1's widths, 5 latent
    layers, 64 slots of 14,336 positions, bfloat16) compiled for the
    v5e.  The decode program: one hvd.mla_decode custom call a latent
    layer over its leaf of 640 lanes (576 and zeros: a leaf of 576 lay
    position-minor and was copied every step), taking it as it lies and
    writing the step's row (no ``while`` loop over the slots writes a
    latent row), one hvd.moe_experts an expert layer; it updates the
    whole cache in place with no copy of a leaf among its temporaries.
    The longest prefill, 10,240 tokens, runs its expert products in
    chunks and fits beside the weights and the cache."""
    from horovod_tpu.models import hybrid, moe
    from horovod_tpu.ops import decode_attention as da
    from horovod_tpu.ops import mla
    from horovod_tpu.serving import ServeConfig, slotcache
    from horovod_tpu.serving.replica import _decode_model_cfg

    sys.path.insert(0, os.path.join(REPO, "benchmarks", "chip"))
    import run as harness
    for module in (da, moe, mla):           # the target, not the CPU
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    file = harness.load_json(harness.HERE, "configs", "A.X-K1.serve.json")
    serve = {**file["serve"],
             "warmup_buckets": tuple(file["serve"]["warmup_buckets"])}
    cfg = ServeConfig(model_cfg=hybrid.HybridConfig(
        **harness.build_args(file)), **serve)
    slots, max_seq = cfg.slots, cfg.max_seq
    assert (slots, max_seq) == (64, 14336)
    model = hybrid.HybridLM(_decode_model_cfg(cfg))
    cache = slotcache.DenseSlotCache(cfg, cfg.model_cfg.family, model, {})

    placed = functools.partial(_placed, sharding=v5e)
    params = placed(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))
    tree = placed(jax.eval_shape(cache._init_cache_impl, params))
    leaf = slots * max_seq * 640 * 2
    assert tree["layer_1"]["attn"]["latent"].shape == (slots, max_seq, 640)
    assert _nbytes(tree) == 5 * (leaf + slots * 4)
    assert 6.98e9 < _nbytes(params) < 6.99e9
    compiled = cache._decode_jit.lower(
        params, tree, *placed((jnp.zeros(slots + 4, jnp.int32),
                               jnp.zeros(slots, jnp.int32),
                               jnp.zeros(slots, bool)))).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if MOSAIC in line]
    latent = [call for call in calls if "hvd.mla_decode" in call]
    assert len(latent) == 5 and len(calls) == 5 + 4
    assert all(call.count("bf16[64,14336,640]") == 2 for call in latent)
    assert sum("hvd.moe_experts" in call for call in calls) == 4
    assert not _loops_over(text, "bf16[64,14336,")
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= _nbytes(tree)
    assert memory.temp_size_in_bytes < leaf // 20
    prefill = cache._prefill_jit.lower(
        params, placed(jnp.zeros((1, 10240), jnp.int32)),
        placed(jnp.zeros((), jnp.int32))).compile().memory_analysis()
    # 15.75 GiB usable: 16.91 GB.
    assert _nbytes(params) + _nbytes(tree) + prefill.temp_size_in_bytes \
        + prefill.output_size_in_bytes < 15.5e9


def test_the_ouro_decode_program_attends_through_the_kernel_every_pass(
        v5e, monkeypatch):
    """``ouro26b_serve_shortreason_sat``'s decode program (Ouro-2.6B's
    widths, 48 layers run 4 times, 8 slots of 640 positions, bfloat16)
    compiled for the v5e: every (pass, layer) keeps leaves of its own,
    ``[8, 640, 16, 128]``, and one hvd.decode_attend custom call each,
    192, takes them as they lie and writes the step's row (no ``while``
    loop over the slots); the program updates the whole cache in place
    and fits beside the weights."""
    from horovod_tpu.models import hybrid
    from horovod_tpu.ops import decode_attention as da
    from horovod_tpu.serving import ServeConfig, slotcache
    from horovod_tpu.serving.replica import _decode_model_cfg

    sys.path.insert(0, os.path.join(REPO, "benchmarks", "chip"))
    import run as harness
    monkeypatch.setattr(da, "_on_tpu", lambda: True)   # the target
    file = harness.load_json(harness.HERE, "configs", "Ouro-2.6B.serve.json")
    serve = {**file["serve"],
             "warmup_buckets": tuple(file["serve"]["warmup_buckets"])}
    cfg = ServeConfig(model_cfg=hybrid.HybridConfig(
        **harness.build_args(file)), **serve)
    slots, max_seq = cfg.slots, cfg.max_seq
    assert (slots, max_seq) == (8, 640)
    model = hybrid.HybridLM(_decode_model_cfg(cfg))
    cache = slotcache.DenseSlotCache(cfg, cfg.model_cfg.family, model, {})

    placed = functools.partial(_placed, sharding=v5e)
    params = placed(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))
    tree = placed(jax.eval_shape(cache._init_cache_impl, params))
    leaf = slots * max_seq * 16 * 128 * 2            # one key leaf, bytes
    attn = tree["layer_47"]["attn"]
    assert attn["pass_4"]["cached_key"].shape == attn["cached_value"].shape \
        == (slots, max_seq, 16, 128)
    assert _nbytes(tree) == 192 * (2 * leaf + slots * 4)
    assert _nbytes(params) == 5_335_945_216
    compiled = cache._decode_jit.lower(
        params, tree, *placed((jnp.zeros(slots, jnp.int32),
                               jnp.zeros(slots, jnp.int32),
                               jnp.zeros(slots, bool)))).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if MOSAIC in line]
    assert len(calls) == 192
    assert all("hvd.decode_attend" in call
               and call.count("bf16[8,640,16,128]") == 4 for call in calls)
    assert not _loops_over(text, "bf16[8,640,")
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= _nbytes(tree)
    assert memory.temp_size_in_bytes < 0.5e9
    # 15.75 GiB usable: 16.91 GB.
    assert _nbytes(params) + _nbytes(tree) + memory.temp_size_in_bytes \
        < 14e9


def test_fit_block_follows_the_tpu_tiling_rule():
    assert fa._fit_block(2048, 1024) == 1024
    assert fa._fit_block(2000, 128) == 80       # not 125
    assert fa._fit_block(96, 128) == 96         # whole sequence
    assert fa._fit_block(100, 128) == 100
    for t, block in ((2001, 128), (2000, 4), (100, 64)):
        with pytest.raises(ValueError, match="multiple of 8"):
            fa._fit_block(t, block)
    # Same refusal through the public entry point, on the CPU.
    q = jnp.zeros((1, 100, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 8"):
        fa.flash_attention(q, q, q, block_q=64, block_k=64)


def test_workers_sharing_a_host_do_not_form_a_jax_world(monkeypatch,
                                                        hvd_log):
    """One process per chip: off the CPU pin, `auto` keeps local workers
    on the host planes and says so; forcing the world is an error that
    names the cause.  One worker per host still forms it."""
    from horovod_tpu.parallel import multihost

    monkeypatch.delenv("HOROVOD_JAX_DISTRIBUTED", raising=False)
    assert multihost.should_init(4, local_size=4) is False   # cpu-pinned
    monkeypatch.setenv("JAX_PLATFORMS", "")
    assert multihost.should_init(4, local_size=4) is False
    assert "does not open the accelerator" in hvd_log.text
    assert multihost.should_init(4, local_size=1) is True
    assert multihost.should_init(1, local_size=1) is False
    monkeypatch.setenv("HOROVOD_JAX_DISTRIBUTED", "1")
    with pytest.raises(RuntimeError, match="one process at a time"):
        multihost.should_init(4, local_size=2)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")               # the tests
    assert multihost.should_init(4, local_size=2) is True


def test_mesh_fallback_names_the_lost_topology(monkeypatch, hvd_log):
    from jax.experimental import mesh_utils

    from horovod_tpu.parallel import MeshSpec, build_mesh

    def refuse(*_, **__):
        raise ValueError("no torus for you")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", refuse)
    mesh = build_mesh(MeshSpec(dp=8))
    assert mesh.shape["dp"] == 8
    assert "ValueError: no torus for you" in hvd_log.text
    assert "enumeration order" in hvd_log.text


def test_dry_run_exercises_every_leg(children):
    """--dry-run-cpu walks all three legs plus the multi-device checks on
    two virtual devices, and every line says it is a dry run — the last
    line is therefore NOT the bare JSON a chip run ends with."""
    rc, out, err = children.result("dry_run", timeout=240)
    assert rc == 0, err[-3000:]
    lines = out.strip().splitlines()
    assert all(ln.startswith("DRY RUN (cpu) ") for ln in lines), lines
    legs = [ln.split()[4] for ln in lines if "smoke-observation" in ln]
    assert legs == ["gpt_train", "gpt_cross_check", "resnet_train",
                    "before_serve", "serve_dense", "serve_paged",
                    "serve_layouts_agree"]
    assert lines[-1].endswith('{"ok": true, "device": {"platform": "cpu", '
                              '"kind": "cpu", "count": 2}}')
    # The run leaves nothing behind that .gitignore does not list.
    assert _git_status() == children.tree_before

#!/usr/bin/env python3
"""The chip benchmark's one entry point: one cell, once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything
that belongs to it is data found by name: its configuration
(``configs/<config>.json``, which also names the driver, ``train`` or
``serve``), its traffic mix (``traffic/<traffic>.json``) and, for a traced
run, one reader per per-layer metric (``layer_metrics/<metric>.json``).
Inputs and weights come from ``--seed``; every shape is warmed before the
window opens and that time is ``setup_s``; the last line of standard
output is the one JSON result.  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.

Other modes, never part of a timed run:
  --rehearse-cpu     the same control flow at the toy sizes the data
                     files give under "rehearsal", on virtual CPU
                     devices; the line is stamped cpu and is no
                     measurement
  --check reference  the configuration against its plain float32
                     reference at the published widths: the function
                     its file names under "reference"
  --check mesh       mesh against one-device losses on one global batch
  --check control    a run of the cell whose comparison also reads its
                     control, the reference in the precision below the
                     configuration's: exit 0 only if the run is correct
                     and the control comes out over the limit
"""
from __future__ import annotations

import time

T_START = time.perf_counter()      # process start, as near as Python gets

import argparse                    # noqa: E402
import importlib                   # noqa: E402
import json                        # noqa: E402
import math                        # noqa: E402
import os                          # noqa: E402
import re                          # noqa: E402
import sys                         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)


MARKS: dict[str, float] = {}       # name -> seconds since the process began


def mark(name: str) -> None:
    """Where set-up time goes, for the notes line."""
    MARKS[name] = round(time.perf_counter() - T_START, 3)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def merged(base: dict, over: dict) -> dict:
    """``over`` laid on ``base``, group by group."""
    out = dict(base)
    for key, value in over.items():
        out[key] = merged(out[key], value) \
            if isinstance(value, dict) and isinstance(out.get(key), dict) \
            else value
    return out


def resolve(ref: str):
    """``package.module:name`` -> the object; a bare ``module:name`` is a
    module of the benchmark's own directory."""
    module, _, name = ref.partition(":")
    return getattr(importlib.import_module(module), name)


def build_args(config: dict) -> dict:
    """A model's constructor arguments: ``args_from`` maps an argument to
    one of the configuration's published keys, ``args`` gives the rest;
    ``"@module:name"`` is an object, a list a tuple."""
    model = config["model"]
    args = {arg: config[key] for arg, key in model["args_from"].items()}
    args.update(model.get("args", {}))
    for arg, value in args.items():
        if isinstance(value, str) and value.startswith("@"):
            args[arg] = resolve(value[1:])
        elif isinstance(value, list):
            args[arg] = tuple(value)
    return args


class CompileCounter:
    """Backend compiles and cache loads, from jax.monitoring; a window
    that sees one is not steady."""

    def __init__(self) -> None:
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class Run:
    """What a driver is handed: the cell's data, the devices, the clock."""

    def __init__(self, args, manifest: dict, cell: dict,
                 devices: list) -> None:
        entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
        self.config = load_json(ROOT, entry["file"])
        self.traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
        self.rehearsal = args.rehearse_cpu
        if self.rehearsal:
            self.config = merged(self.config, self.config["rehearsal"])
            self.traffic = merged(self.traffic, self.traffic["rehearsal"])
        self.seed, self.seconds = args.seed, args.seconds
        self.devices = devices[:cell["chips"]]
        self.t_start, self.mark = T_START, mark
        import tracing
        self.tracer = tracing.Tracer(bool(args.trace))
        self.compiles = CompileCounter()
        # The cell's metrics, name -> unit, as the manifest has them.
        self.units = {
            kind: {m["name"]: m["unit"] for m in manifest[kind]
                   if cell["name"] in m.get("workloads", [cell["name"]])}
            for kind in ("end_to_end", "per_layer")}

    def say(self, text: str) -> None:
        print(("REHEARSAL (cpu) " if self.rehearsal else "") + text,
              flush=True)

    def count(self, name: str):
        """The function behind one of the configuration's named operation
        or byte counts."""
        return resolve(self.config["counts"][name])

    resolve = staticmethod(resolve)    # for a module that a file names

    def model_config(self, **overrides):
        """The model's configuration object, where its class takes one
        (and for a driver that hands the program that, not a model)."""
        return resolve(self.config["model"]["config"])(
            **{**build_args(self.config), **overrides})

    def build_model(self, **overrides):
        """The configuration's model through the program's own classes."""
        model = resolve(self.config["model"]["class"])
        if "config" in self.config["model"]:
            return model(self.model_config(**overrides))
        return model(**{**build_args(self.config), **overrides})


def fullest(devices: list) -> dict:
    """The allocator's statistics of the chip whose peak is highest, as
    JAX reports them (a CPU reports none)."""
    return max(((d.memory_stats() or {}) for d in devices),
               key=lambda stats: stats.get("peak_bytes_in_use", 0))


def device_stamp(devices: list) -> dict:
    """The device as JAX reports it.  ``memory_peak_bytes`` is the
    allocator's ``peak_bytes_in_use`` on the fullest chip, read and not
    computed: it holds every buffer (weights, state, batch, caches) and
    leaves out what a program needs only while it runs."""
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": fullest(devices).get("peak_bytes_in_use",
                                                      0)}


def layer_metrics(run: Run, result: dict) -> tuple[dict, dict, dict]:
    """The traced run's per-layer metrics, the device's busy seconds and
    the breakdown, all from the profiler's sessions."""
    import counts
    import tracing
    timeline = tracing.load(run.tracer.files)
    facts = {"timeline": timeline, "labels": run.tracer.labels,
             "counters": result["counters"], "metrics": {},
             # A CPU has no peak worth a share: a rehearsal reads none.
             "peaks": {} if run.rehearsal
             else counts.peaks(run.devices[0].device_kind)}
    pending = {name: load_json(HERE, "layer_metrics", name + ".json")
               for name in run.units["per_layer"]}
    while pending:                     # a metric may read another one
        read = {name: value for name, spec in pending.items()
                if (value := tracing.evaluate(spec["reader"], facts))
                is not None}
        if not read:
            break                      # nothing to read: left out
        facts["metrics"].update(read)
        for name in read:
            del pending[name]
    metrics = {name: {"value": value, "unit": run.units["per_layer"][name]}
               for name, value in facts["metrics"].items()}
    busy_s, window_s = tracing.device_busy(timeline, run.tracer.windows)
    return (metrics, {"busy_s": busy_s, "window_s": window_s},
            tracing.breakdown(timeline, run.devices[0].id))


def parse(argv) -> argparse.Namespace:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None,
                        help="default: run_seconds of BENCHMARK.json")
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse-cpu", action="store_true")
    parser.add_argument("--check", choices=("reference", "mesh", "control"))
    return parser.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    manifest = load_json(ROOT, "BENCHMARK.json")
    if args.seconds is None:
        args.seconds = manifest["run_seconds"]
    cell = next((w for w in manifest["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"chip benchmark: BENCHMARK.json has no workload "
              f"{args.workload!r}", file=sys.stderr)
        return 1
    chips = cell["chips"]
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        count = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                          flags)
        if not count or int(count.group(1)) < chips:
            flags = flags.replace(count.group(0), "") if count else flags
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={chips}"
            ).strip()

    import jax
    mark("import_jax")
    backend = jax.default_backend()        # opens the chips, once
    devices = jax.devices()
    mark("chips_open")
    if backend != ("cpu" if args.rehearse_cpu else "tpu") \
            or len(devices) < chips:
        print(f"chip benchmark: {args.workload} needs {chips} TPU chip(s); "
              f"jax.default_backend() is {backend!r} with {len(devices)} "
              "device(s).  There is no CPU fallback; --rehearse-cpu only "
              "walks the control flow.", file=sys.stderr)
        return 1

    from horovod_tpu.common.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    if not args.rehearse_cpu:
        # Small programs too: a run after the first compiles nothing.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    run = Run(args, manifest, cell, devices)
    mark("program_imported")
    run.say(f"{args.workload}: {len(run.devices)} x "
            f"{devices[0].device_kind} ({backend}), seed {args.seed}, "
            f"{args.seconds:g} s, trace {args.trace}, compile cache "
            f"{cache_dir}")
    try:
        if args.check in ("reference", "mesh"):
            import reference
            return reference.check(run, args.check)
        driver = importlib.import_module(run.config["driver"])
        result = driver.drive(run)
        device = device_stamp(run.devices)
        # The comparison with the plain reference comes once the window
        # has closed, the peak has been read and the program's state is
        # freed: a process's peak never falls again.
        if args.check == "control":
            result["after"](result, control=True)
            seen = result["notes"]["served_check"]
            seen["ok"] = bool(not result["problems"] and any(
                seen["control_" + key] > limit
                for key, limit in seen["limits"].items()))
            run.say("check " + json.dumps(
                {**seen, "problems": result["problems"]}))
            return 0 if seen["ok"] else 1
        if "after" in result:
            result["after"](result)
        line = {"correct": not result["problems"],
                "attempted": result["attempted"],
                "failed": result["attempted"] if result["problems"] else 0}
        if args.trace:
            line["metrics"], busy, line["breakdown"] = \
                layer_metrics(run, result)
            device.update(busy)
        else:
            line["metrics"] = {
                name: {"value": result["end_to_end"][name], "unit": unit}
                for name, unit in run.units["end_to_end"].items()}
        line["device"] = device
        if run.rehearsal:
            line["rehearsal"] = True
        run.say("notes " + json.dumps(
            {"problems": result["problems"], "marks_s": MARKS,
             "allocator": fullest(run.devices), **result["notes"]}))
        # Each number that decided ``correct`` beside its limit: the last
        # lines of standard error, and the last key of the result.
        line["compared"] = {
            name: {"value": value if math.isfinite(value) else str(value),
                   "limit": limit}
            for name, (value, limit) in result["compared"].items()}
        for name, pair in line["compared"].items():
            print(f"compared {name}: {pair['value']} (limit "
                  f"{pair['limit']})", file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(line), flush=True)
        return 0
    finally:
        run.tracer.close()


if __name__ == "__main__":
    sys.exit(main())

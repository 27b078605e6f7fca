"""The serve driver: clients in a closed loop on one ``ReplicaExecutor``.

The driver is the clients, the ingress and the loop: it submits to the
executor's queue and calls ``ReplicaExecutor._serve_step`` one step at a
time, as ``serve_loop`` does, so that every step has a host time and a
token count.  A client's next request enters the queue when its last one
completes.  The request table (prompt and output lengths, paired and
ordered) is the traffic file's and is the same for every seed; ``--seed``
makes the weights and the token ids only, and with random weights and no
EOS the content cannot change the work.

A token is delivered when the step that emitted it returns to the host.
A prompt's tokens count in the step that prefills it, a generated token
in the step that emits it, and a gap is the time between two consecutive
deliveries of one stream (the two tokens an admitting step emits for its
new stream arrive together and make no gap).  The window opens a fixed
number of steps after the last client's first admit and closes at the
first step boundary past ``--seconds``.

``correct``.  The weights are the benchmark's own, from the seed, handed
to the replica as a deployment hands it a checkpoint (the configuration's
``served_check.weights``).  Once the window has closed, the peak has
been read and the replica is freed, ``compare_served`` takes a sample,
drawn from the seed, of the requests that finished inside the window,
the longest among them, runs the configuration's plain reference once
over each one's prompt and served tokens, and holds the widest gap by
which a served token's logit lies below the reference's best to the
configuration's limit.
"""
from __future__ import annotations

import functools
import math
import random
import statistics
import time
from typing import NamedTuple

HISTOGRAM_EDGES_MS = (0, 20, 40, 60, 80, 100, 150, 200, 250, 500, math.inf)


class Step(NamedTuple):
    """One serve step as the driver saw it."""
    end: float            # host clock when the step returned
    prompt_tokens: int    # of the requests it admitted
    new_tokens: int       # generated tokens it delivered
    gaps: int             # streams it delivered to that had a token before
    occupied: int         # slots still decoding after it
    admits: int


# ------------------------------------------------------------ the yardstick
def request_tokens(seed: int, index: int, length: int, vocab: int) -> list:
    """The ``index``-th request's prompt: ids from the seed alone."""
    rng = random.Random(seed * 1_000_003 + index)
    return [rng.randrange(2, vocab) for _ in range(length)]


def first_output(length: int, client: int, clients: int) -> int:
    """A client's first request is cut to (client + 1) / clients of its
    output, so that completions are spread from the start."""
    return max(2, math.ceil(length * (client + 1) / clients))


def weighted_percentile(pairs: list, q: float) -> float:
    """Nearest-rank percentile of values that come with a count each."""
    total = sum(count for _, count in pairs)
    rank, seen = math.ceil(q * total), 0
    for value, count in sorted(pairs):
        seen += count
        if seen >= rank:
            return value
    raise ValueError("no samples")


def per_step_counts(run) -> dict:
    """The configuration's counts whose key ends in ``_per_step``, key ->
    function of ``(config, contexts)``: the bytes or operations one
    decode step needs, for a kernel's share of its roofline."""
    return {key: run.count(key) for key in run.config.get("counts", {})
            if key.endswith("_per_step")}


def counters(seen: dict, slots: int, per_step: dict, stats: dict) -> dict:
    """What a ``counter`` reader can name: the driver's slot occupancy,
    the mean of every ``_per_step`` count over the traced steps that
    admitted nothing (None where there was none, so that its metric is
    left out), and every plain number of the executor's ``stats`` as
    ``stats.<key>``."""
    found = {"slot_occupancy_pct":
             100.0 * seen["occupied_slot_steps"] / (seen["steps"] * slots)}
    found.update((key, statistics.fmean(values) if values else None)
                 for key, values in per_step.items())
    found.update((f"stats.{key}", value) for key, value in stats.items()
                 if type(value) in (int, float))
    return found


def account(log: list, opened: float, closed: float) -> dict:
    """Totals, rates and the gap tail of the steps that ended inside
    (opened, closed]; ``log`` is every Step of the run, in order."""
    elapsed = closed - opened
    prompt = new = admits = admit_steps = occupied = 0
    gaps: list = []
    plain: list = []
    steps = 0
    for before, step in zip(log, log[1:]):
        if not opened < step.end <= closed:
            continue
        steps += 1
        prompt += step.prompt_tokens
        new += step.new_tokens
        admits += step.admits
        admit_steps += bool(step.admits)
        occupied += step.occupied
        duration = step.end - before.end
        if step.gaps:
            gaps.append((duration * 1e3, step.gaps))
        if not step.admits:
            plain.append(duration * 1e3)
    histogram = [[lo, hi if hi != math.inf else None,
                  sum(n for ms, n in gaps if lo <= ms < hi)]
                 for lo, hi in zip(HISTOGRAM_EDGES_MS, HISTOGRAM_EDGES_MS[1:])]
    return {"elapsed_s": elapsed, "steps": steps, "admits": admits,
            "admit_steps": admit_steps, "prompt_tokens": prompt,
            "output_tokens": new,
            "total_tokens_per_s": (prompt + new) / elapsed,
            "gap_samples": sum(n for _, n in gaps),
            "itl_ms_p50": weighted_percentile(gaps, 0.50),
            "itl_ms_p95": weighted_percentile(gaps, 0.95),
            "plain_step_ms_p50": statistics.median(plain) if plain else None,
            "occupied_slot_steps": occupied,
            "gap_histogram_ms": [row for row in histogram if row[2]]}


# ----------------------------------------------------------------- the loop
class ClosedLoop:
    """The clients: who waits for which request, and what comes next."""

    def __init__(self, run, executor, slo_ms: float) -> None:
        self.run, self.executor, self.slo_ms = run, executor, slo_ms
        self.table = run.traffic["requests"]
        self.clients = run.traffic["clients"]
        self.vocab = run.config["vocab_size"]
        self.issued = 0
        self.client_of: dict[int, int] = {}      # rid -> client
        self.expected: dict[int, int] = {}       # rid -> output length
        self.prompt_len: dict[int, int] = {}
        self.index_of: dict[int, int] = {}       # rid -> place in the table
        self.delivered: dict[int, int] = {}      # rid -> tokens so far
        self.finished_at: dict[int, float] = {}  # rid -> host clock
        self.shed = 0
        self.log: list[Step] = []

    def sample(self, opened: float, closed: float, count: int) -> list:
        """``count`` of the requests that finished inside the window, as
        (prompt tokens, served tokens): the longest and a draw from the
        seed among the others."""
        done = sorted(rid for rid, at in self.finished_at.items()
                      if opened < at <= closed)
        size = lambda rid: self.prompt_len[rid] + self.expected[rid]  # noqa
        if not done:
            return []
        longest = max(done, key=size)
        others = [rid for rid in done if rid != longest]
        picked = [longest] + random.Random(self.run.seed).sample(
            others, min(count - 1, len(others)))
        return [(request_tokens(self.run.seed, self.index_of[rid],
                                self.prompt_len[rid], self.vocab),
                 list(self.executor.completed[rid]["generated"]))
                for rid in picked]

    def submit(self, client: int) -> None:
        prompt, output = self.table[self.issued % len(self.table)]
        if self.issued < self.clients:
            output = first_output(output, client, self.clients)
        tokens = request_tokens(self.run.seed, self.issued, prompt,
                                self.vocab)
        self.issued += 1
        self.executor.stats["offered"] += 1
        rid = self.executor.queue.submit(tokens, output, self.slo_ms)
        if rid is None:
            self.shed += 1
            return
        self.client_of[rid], self.expected[rid] = client, output
        self.prompt_len[rid], self.index_of[rid] = prompt, self.issued - 1

    def step(self) -> Step:
        """One serve step, what it delivered, and the clients' answers."""
        ex = self.executor
        with self.run.tracer.span("serve.step") as label:
            ex._serve_step()
        end = time.perf_counter()
        now = {s.rid: len(s.generated) for s in ex.slots if s is not None}
        finished = [rid for rid in ex.completed
                    if rid not in self.finished_at]
        now.update((rid, ex.completed[rid]["tokens"]) for rid in finished)
        prompt = new = gaps = admits = 0
        for rid, count in now.items():
            before = self.delivered.get(rid, 0)
            if before == 0:
                admits += 1
                prompt += self.prompt_len[rid]
            elif count > before:
                gaps += 1
            new += count - before
            self.delivered[rid] = count
        label[0] = "admit" if admits else "decode"
        for rid in finished:
            self.finished_at[rid] = end
            self.submit(self.client_of[rid])
        step = Step(end, prompt, new, gaps,
                    sum(s is not None for s in ex.slots), admits)
        self.log.append(step)
        return step

    def contexts(self) -> list[int]:
        """Live context of every decoding slot, in tokens."""
        return [self.prompt_len[s.rid] + len(s.generated)
                for s in self.executor.slots if s is not None]

    def problems(self) -> tuple[list[str], dict]:
        """What went wrong, in words, and each number that was compared
        beside its limit."""
        ex, found = self.executor, []
        wrong = {rid: ex.completed[rid]["tokens"] for rid in self.finished_at
                 if ex.completed[rid]["tokens"] != self.expected[rid]}
        if wrong:
            found.append(f"requests without exactly their output length: "
                         f"{wrong}")
        outcomes = ex.admission.outcome_totals()
        lost = {"shed_at_ingress": self.shed,
                "shed": outcomes.get("shed", 0),
                "expired": ex.stats["expired"] + outcomes.get("expired", 0),
                "lost": ex.stats["lost"]}
        if any(lost.values()):
            found.append(f"requests shed, expired or lost: {lost}")
        return found, {"requests_off_their_length": [len(wrong), 0],
                       "requests_shed_expired_lost":
                           [int(sum(lost.values())), 0]}


def compare_served(run, params, sample: list, result: dict,
                   control: bool = False) -> None:
    """The sampled requests against the configuration's plain reference:
    the widest and the mean gap by which a served token's logit lies
    below the reference's best, each beside its limit in
    ``result["compared"]`` and a problem where it is over.  ``control``
    also reads the gaps of the tokens that the precision below the
    configuration's would put first, which have to come out over a
    limit."""
    import numpy as np

    check = run.config["served_check"]
    gap_of = run.resolve(check["gap"])(run.config, control)
    # One shape: the longest request the traffic's table can make.
    pad = -(-max(p + o for p, o in run.traffic["requests"]) // 256) * 256
    served = sum(len(tokens) for _, tokens in sample)
    widest: dict[str, float] = {}
    total: dict[str, float] = {}
    for prompt, tokens in sample:
        padded = np.zeros((1, pad), np.int32)
        padded[0, :len(prompt) + len(tokens)] = prompt + tokens
        read = gap_of(params, padded, np.int32(len(prompt)),
                      np.int32(len(prompt) + len(tokens)))
        for key, value in read.items():
            if key.endswith("_sum"):
                total[key] = total.get(key, 0.0) + float(value)
            else:
                widest[key] = max(widest.get(key, 0.0), float(value))
    # No finished request is nothing compared, and reads as a fault.
    numbers = {**widest, **{key[:-4] + "_mean": value / served
                            for key, value in total.items()}} \
        or {"gap": math.inf, "gap_mean": math.inf}
    result["notes"]["served_check"] = {
        **numbers, "limits": check["limits"], "requests": len(sample),
        "served_tokens": served}
    for key, limit in check["limits"].items():
        result["compared"]["served_logit_" + key] = [numbers[key], limit]
        if not numbers[key] <= limit:
            result["problems"].append(
                f"served tokens' logits lie below the reference's best "
                f"by a {key} of {numbers[key]}, over the limit of {limit} "
                f"({len(sample)} requests, {served} tokens)")


def drive(run) -> dict:
    import horovod_tpu as hvd
    from horovod_tpu.serving import ReplicaExecutor, ServeConfig

    cfg, traffic = run.config, run.traffic
    serve = cfg["serve"]
    hvd.init()        # size 1, no rendezvous: the exchanges stay local
    run.mark("hvd")
    check = cfg["served_check"]
    params = run.resolve(check["weights"])(run)
    run.mark("weights")
    # The whole ``serve`` group: a configuration sets any field the
    # program's ServeConfig has.
    executor = ReplicaExecutor(ServeConfig(
        model_cfg=run.model_config(), seed=run.seed,
        **{**serve, "warmup_buckets": tuple(serve["warmup_buckets"])}),
        params=params)
    run.mark("executor")
    try:
        loop = ClosedLoop(run, executor, serve["slo_ms"])
        loop.log.append(Step(time.perf_counter(), 0, 0, 0, 0, 0))
        for client in range(loop.clients):
            loop.submit(client)
        admitted = 0
        while admitted < loop.clients:           # fill the slots
            admitted += loop.step().admits
        for _ in range(traffic["warmup_steps"]):
            loop.step()
        run.mark("filled")

        compiles0 = run.compiles.count
        opened = loop.log[-1].end
        first = len(loop.log)
        count_of = per_step_counts(run)
        # Of the traced steps that admit nothing, count by count.
        per_step: dict[str, list] = {key: [] for key in count_of}
        with run.tracer.window([d.id for d in run.devices]):
            while True:
                step = loop.step()
                if not run.tracer.enabled:
                    if step.end - opened >= run.seconds:
                        break
                    continue
                if not step.admits:
                    contexts = loop.contexts()
                    for key, count in count_of.items():
                        per_step[key].append(count(cfg, contexts))
                if len(loop.log) - first >= traffic["trace_steps"]:
                    break
        closed = loop.log[-1].end
        compiled_inside = run.compiles.count - compiles0

        seen = account(loop.log, opened, closed)
        problems, compared = loop.problems()
        compared["compilations_in_window"] = [compiled_inside, 0]
        if compiled_inside:
            problems.append(f"{compiled_inside} compilations inside the "
                            "window")
        attempted = sum(opened < at <= closed
                        for at in loop.finished_at.values())
        sample = loop.sample(opened, closed, check["requests"])
    finally:
        executor.close()
        hvd.shutdown()
    counted = counters(seen, len(executor.slots), per_step, executor.stats)
    submitted, completed = loop.issued, len(loop.finished_at)
    del executor, loop        # the cache goes before the reference runs
    end_to_end = {"serve_total_tokens_per_s": seen["total_tokens_per_s"],
                  "serve_itl_ms_p95": seen["itl_ms_p95"],
                  "setup_s": opened - run.t_start}
    return {"problems": problems, "attempted": attempted,
            "end_to_end": end_to_end, "compared": compared,
            "after": functools.partial(compare_served, run, params, sample),
            "counters": counted,
            "notes": {**seen, **counted, "submitted": submitted,
                      "completed": completed,
                      "compiles_total": run.compiles.count}}

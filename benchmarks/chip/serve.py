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
"""
from __future__ import annotations

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
        self.delivered: dict[int, int] = {}      # rid -> tokens so far
        self.finished_at: dict[int, float] = {}  # rid -> host clock
        self.shed = 0
        self.log: list[Step] = []

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
        self.prompt_len[rid] = prompt

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

    def problems(self) -> list[str]:
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
        return found


def drive(run) -> dict:
    import horovod_tpu as hvd
    from horovod_tpu.serving import ReplicaExecutor, ServeConfig

    cfg, traffic = run.config, run.traffic
    serve = cfg["serve"]
    hvd.init()        # size 1, no rendezvous: the exchanges stay local
    run.mark("hvd")
    executor = ReplicaExecutor(ServeConfig(
        model_cfg=run.model_config(), seed=run.seed,
        max_batch=serve["max_batch"], max_seq=serve["max_seq"],
        token_budget=serve["token_budget"], paged=serve["paged"],
        eos_id=serve["eos_id"], slo_ms=serve["slo_ms"],
        queue_depth=serve["queue_depth"],
        warmup_buckets=tuple(serve["warmup_buckets"])))
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
        decode_bytes = []      # of the traced steps that admit nothing
        with run.tracer.window([d.id for d in run.devices]):
            while True:
                step = loop.step()
                if not run.tracer.enabled:
                    if step.end - opened >= run.seconds:
                        break
                    continue
                if not step.admits:
                    decode_bytes.append(run.count("decode_bytes_per_step")(
                        cfg, loop.contexts()))
                if len(loop.log) - first >= traffic["trace_steps"]:
                    break
        closed = loop.log[-1].end
        compiled_inside = run.compiles.count - compiles0

        seen = account(loop.log, opened, closed)
        problems = loop.problems()
        if compiled_inside:
            problems.append(f"{compiled_inside} compilations inside the "
                            "window")
        attempted = sum(opened < at <= closed
                        for at in loop.finished_at.values())
    finally:
        executor.close()
        hvd.shutdown()
    slots = len(executor.slots)
    counters = {
        "slot_occupancy_pct":
            100.0 * seen["occupied_slot_steps"] / (seen["steps"] * slots),
        "decode_bytes_per_step":
            statistics.fmean(decode_bytes) if decode_bytes else None}
    end_to_end = {"serve_total_tokens_per_s": seen["total_tokens_per_s"],
                  "serve_itl_ms_p95": seen["itl_ms_p95"],
                  "setup_s": opened - run.t_start}
    return {"problems": problems, "attempted": attempted,
            "failed": attempted if problems else 0,
            "end_to_end": end_to_end,
            "counters": counters,
            "notes": {**seen, **counters, "submitted": loop.issued,
                      "completed": len(loop.finished_at),
                      "compiles_total": run.compiles.count}}

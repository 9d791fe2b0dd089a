"""One run of one cell: set-up, the measured window, the check, the result.

Set-up makes the weights on the device from the seed, builds the engine as
``repro.launch.serve.make_engine`` builds it (``class_sharded="off"``, no
tuning cache), warms every prefill length the cell's traffic can produce
and the decode step by running them, and admits the mix's set-up sessions.

The window drives ``ServingEngine.submit / admit / step`` from one host
loop in this process: arrivals that are due are submitted, then ``admit()``
if anything is queued, then ``step()`` if any slot is busy.  Each token is
time-stamped on the host when the call that produced it returns (both
calls block until their tokens are on the host).  A ``jax.monitoring``
listener counts program compilations and cache loads inside the window.
With ``trace`` the profiler records the window's last ``TRACE_SPAN_S``
seconds, with a host span around every call and every turn of the loop.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from chipbench import check, traffic
from chipbench.model import Dims, Geometry, arch_config, make_params
from chipbench.spec import Cell

TRACE_SPAN_S = 15.0
# The served sample the check compares: the longest request, one of every
# slot that served, and at least this many tokens and requests.
CHECK_MIN_TOKENS = 400
CHECK_MIN_REQUESTS = 2
# A program compiled, or loaded from the persistent cache: either stalls
# the window.
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


@dataclasses.dataclass
class Call:
    kind: str                 # "admit" | "step"
    t0: float
    t1: float
    rows: int                 # slot-table rows the program ran over
    round_len: int = 0        # admit: the round's longest prompt (scan length)
    contexts: tuple = ()      # keys each produced token attended to


@dataclasses.dataclass
class Record:
    t_start: float                                   # process start
    t0: float = 0.0                                  # window open
    t1: float = 0.0                                  # window close
    calls: list[Call] = dataclasses.field(default_factory=list)
    token_times: dict = dataclasses.field(default_factory=dict)   # rid -> [t]
    arrival: dict = dataclasses.field(default_factory=dict)       # rid -> scheduled t
    lateness: list = dataclasses.field(default_factory=list)      # s, per arrival
    compiles: int = 0
    trace_t0: Optional[float] = None


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    cell: Cell
    dims: Dims
    geometry: Geometry
    record: Record
    device_kind: str
    trace: object = None      # tracefile.Summary of a traced run

    def calls_in_window(self) -> list[Call]:
        r = self.record
        return [c for c in r.calls if c.t0 >= r.t0]

    def calls_in_trace(self) -> list[Call]:
        t = self.record.trace_t0
        return [] if t is None else [c for c in self.record.calls if c.t0 >= t]


class CompileCounter:
    """Counts compilations (and persistent-cache loads) while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


def use_compile_cache(root: Path) -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``;
    every program is cached, however fast it compiled."""

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build_engine(cfg, params, geometry: Geometry):
    """The engine as ``repro.launch.serve`` builds it from its arguments."""

    from repro.launch import serve

    args = serve.build_parser().parse_args([
        "--arch", cfg.name, "--slots-per-pod", str(geometry.slots_per_pod),
        "--paged", geometry.paged, "--class-sharded", "off",
    ])
    eng = serve.make_engine(cfg, params, serve.serving_mesh(args), args,
                            geometry.lane, geometry.slots_per_pod)
    if eng.n_pods != geometry.pods:
        raise ValueError(f"the engine has {eng.n_pods} pods, the configuration {geometry.pods}")
    return eng


def log(*parts):
    print("chipbench:", *parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------


def warm(eng, lens, vocab: int, rng, *, decode: bool):
    """Run a prefill of each length in ``lens`` (and, with ``decode``, one
    decode step) so that none compiles inside the window.  Each warm
    request retires at once (a budget of one token), but with ``decode`` the
    last takes one decode step first."""

    for i, n in enumerate(lens):
        budget = 2 if decode and i == len(lens) - 1 else 1
        eng.submit(traffic.prompt_ids(rng, n, vocab), budget)
        if eng.admit() != 1:
            raise RuntimeError(f"warm-up admission of a {n}-token prompt failed")
    if decode:
        eng.step()
    if (eng.slot_rid >= 0).any() or any(eng.queues):
        raise RuntimeError("warm-up requests did not all retire")


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------


class Window:
    """The host loop of the measured window and its token bookkeeping."""

    def __init__(self, eng, plan: traffic.Plan, record: Record, *, lane: int, vocab: int,
                 seed: int):
        self.eng = eng
        self.plan = plan
        self.rec = record
        self.lane = lane
        self.vocab = vocab
        self.prompts: dict[int, np.ndarray] = {}
        self.slot_of: dict[int, int] = {}
        self.backlog_rng = traffic.rng_for(seed, "backlog")
        self.n_completed = len(eng.completions)
        self.attempted = 0

    def _new_completions(self):
        new = self.eng.completions[self.n_completed:]
        self.n_completed = len(self.eng.completions)
        return new

    def _stamp(self, rids, t):
        for rid in rids:
            self.rec.token_times.setdefault(rid, []).append(t)

    def submit(self, req: traffic.Req) -> int:
        rid = self.eng.submit(req.prompt, req.max_new, route_class=req.route_class)
        self.prompts[rid] = req.prompt
        self.attempted += 1
        return rid

    def admit(self):
        import jax

        eng = self.eng
        before = set(int(r) for r in eng.slot_rid if r >= 0)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.admit"):
            n = eng.admit()
        t1 = time.perf_counter()
        if n == 0:
            return
        now = {int(r): s for s, r in enumerate(eng.slot_rid) if r >= 0}
        admitted = (set(now) - before) | {c.rid for c in self._new_completions() if c.rid not in before}
        for r in admitted:
            self.slot_of[r] = now.get(r, -1)
        plens = [len(self.prompts[r]) for r in admitted]
        self.rec.calls.append(Call("admit", t0, t1, eng.n_slots, round_len=max(plens),
                                   contexts=tuple(plens)))
        self._stamp(admitted, t1)

    def step(self):
        import jax

        eng = self.eng
        active = [(s, int(r)) for s, r in enumerate(eng.slot_rid) if r >= 0]
        contexts = tuple(len(self.prompts[r]) + len(self.rec.token_times.get(r, ())) for _, r in active)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.step"):
            eng.step()
        t1 = time.perf_counter()
        self.rec.calls.append(Call("step", t0, t1, eng.n_slots, contexts=contexts))
        self._stamp([r for _, r in active], t1)
        retired = {c.rid for c in self._new_completions()}
        if self.plan.backlog:
            classes = eng.asym.pod_class_indices()
            for slot, rid in active:
                if rid in retired:
                    req = traffic.backlog_request(
                        self.plan.backlog, self.backlog_rng, vocab=self.vocab, lane=self.lane,
                        route_class=classes[slot // eng.c_max])
                    self.submit(req)

    def run(self, seconds: float, *, trace_dir: Optional[str], counter: CompileCounter):
        import jax

        eng, rec = self.eng, self.rec
        arrivals = self.plan.arrivals
        i = 0
        rec.t0 = time.perf_counter()
        t_end = rec.t0 + seconds
        t_trace = rec.t0 + max(0.0, seconds - TRACE_SPAN_S)
        counter.armed = True
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if trace_dir is not None and rec.trace_t0 is None and now >= t_trace:
                jax.profiler.start_trace(trace_dir)
                rec.trace_t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("chipbench.client"):
                while i < len(arrivals) and rec.t0 + arrivals[i].at <= now:
                    rid = self.submit(arrivals[i])
                    rec.arrival[rid] = rec.t0 + arrivals[i].at
                    rec.lateness.append(now - rec.arrival[rid])
                    i += 1
            if any(eng.queues):
                self.admit()
            if (eng.slot_rid >= 0).any():
                self.step()
            elif not any(eng.queues):
                nxt = rec.t0 + arrivals[i].at if i < len(arrivals) else t_end
                with jax.profiler.TraceAnnotation("chipbench.wait"):
                    time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))
        rec.t1 = time.perf_counter()
        counter.armed = False
        rec.compiles = counter.count

    def served(self) -> list[check.Served]:
        """Every request served in the run with the tokens it got, checked
        against the host stamps of the window."""

        eng = self.eng
        out = []
        done = {c.rid: c for c in eng.completions}
        for rid, stamps in self.rec.token_times.items():
            prompt = self.prompts[rid]
            toks = done[rid].tokens[len(prompt):] if rid in done else eng.partial_tokens(rid)
            if len(toks) != len(stamps):
                raise RuntimeError(f"request {rid}: {len(toks)} tokens, {len(stamps)} stamped")
            out.append(check.Served(rid, np.asarray(prompt, np.int32), np.asarray(toks, np.int32),
                                    slot=self.slot_of.get(rid, -1)))
        return out


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


def free(*trees):
    import jax

    for x in jax.tree.leaves(trees):
        x.delete()


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, root: Path, t_start: float,
        control_bits: Optional[int] = None,
        engine_hook: Optional[Callable] = None) -> dict:
    """One run of ``cell``; returns the result line's object.

    ``control_bits`` also puts the control in the program's place on the same
    sample and judges it as the program is judged, under ``"control"``
    (``control.py``; the benchmark's own runs never do).
    ``engine_hook(eng)`` may replace parts of the built engine (the tests
    break the timed path through it)."""

    import jax

    from repro.distributed import sharding as SH

    cache = use_compile_cache(root)
    # Blocks come from the analytical derivation, never a tuning cache.
    os.environ.pop("REPRO_TUNING_CACHE", None)
    os.environ.pop("REPRO_TUNING_SPEC", None)
    SH.use_mesh_for_activations(None)
    dev = jax.devices()[0]
    dims = Dims.from_config(cell.config)
    geometry = Geometry.from_config(cell.config)
    cfg = arch_config(cell.config)
    log(f"{cell.name}: {dev.device_kind} x {jax.device_count()}, compile cache {cache}")
    counter = CompileCounter()

    params = make_params(seed, dims)
    eng = build_engine(cfg, params, geometry)
    if engine_hook is not None:
        engine_hook(eng)
    plan = traffic.plan(cell.traffic, seed, seconds, vocab=dims.vocab, lane=geometry.lane,
                        pods=geometry.pods, slots_per_pod=geometry.slots_per_pod)
    # A round of set-up sessions prefills at its own longest prompt and is
    # followed by one decode step: those programs need no warm request.
    session_len = max((len(r.prompt) for r in plan.sessions), default=None)
    warm(eng, [n for n in plan.warm_prompt_lens if n != session_len], dims.vocab,
         traffic.rng_for(seed, "warm"), decode=not plan.sessions)
    rec = Record(t_start=t_start)
    win = Window(eng, plan, rec, lane=geometry.lane, vocab=dims.vocab, seed=seed)
    for req in plan.sessions:
        win.submit(req)
    if plan.sessions:
        win.admit()
        if any(eng.queues):
            raise RuntimeError("the set-up sessions did not all fit in one admission round")
        win.step()
    log(f"set-up {time.perf_counter() - t_start:.3f} s: warmed prompt lengths "
        f"{plan.warm_prompt_lens}, {len(plan.sessions)} sessions, {len(plan.arrivals)} arrivals "
        f"planned")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    win.run(seconds, trace_dir=trace_dir, counter=counter)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    served = win.served()
    attempted = win.attempted
    queued, busy = sum(len(q) for q in eng.queues), int((eng.slot_rid >= 0).sum())
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    summary = None
    if trace_dir is not None:
        import shutil

        from chipbench import tracefile

        (path,) = Path(trace_dir).rglob("*.xplane.pb")
        summary = tracefile.summarize(tracefile.load(path))
        shutil.rmtree(trace_dir, ignore_errors=True)

    run_ = Run(cell=cell, dims=dims, geometry=geometry, record=rec, device_kind=dev.device_kind,
               trace=summary)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.read(run_)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}

    # The check runs on the weights alone, once the engine's state is freed.
    free(eng.state, eng.tokens)
    del eng, win
    gc.collect()
    picked = check.sample(served, traffic.rng_for(seed, "check"),
                          min_tokens=CHECK_MIN_TOKENS, min_requests=CHECK_MIN_REQUESTS)
    t_check = time.perf_counter()
    got = check.gaps(params, dims, geometry.lane, picked, bits=control_bits)
    limits = cell.config["chipbench"]["limits"]
    checks = {
        "widest_gap": {"value": got["widest_gap"], "limit": limits["widest_gap"]},
        "compiles_in_window": {"value": rec.compiles, "limit": 0},
    }
    correct = check.verdict(picked, got["widest_gap"], rec.compiles, limits)
    late = rec.lateness or [0.0]
    log(f"window {rec.t1 - rec.t0:.3f} s, {len(rec.calls)} engine calls, {len(served)} requests "
        f"served, {queued} queued and {busy} in slots at the close; generator lateness max {1e3 * max(late):.3f} ms, median "
        f"{1e3 * float(np.median(late)):.3f} ms over {len(rec.lateness)} arrivals")
    slow = sorted(run_.calls_in_window(), key=lambda c: c.t0 - c.t1)[:5]
    log("longest engine calls in the window: " + ", ".join(
        f"{c.kind}({c.round_len or len(c.contexts)}) {1e3 * (c.t1 - c.t0):.1f} ms at "
        f"{c.t0 - rec.t0:.2f} s" for c in slow))
    log(f"check: {got['requests']} requests over {len({s.slot for s in picked})} slots, "
        f"{got['tokens']} served tokens, argmax agreement "
        f"{got['argmax_agreement']:.4f}, reference {time.perf_counter() - t_check:.3f} s")

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count(),
              "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": 0,
           "metrics": metrics, "device": device}
    if summary is not None:
        from chipbench import tracefile

        device["busy_s"] = summary.busy_ns * 1e-9
        device["window_s"] = summary.window_ns * 1e-9
        out["breakdown"] = tracefile.breakdown(summary)
    if control_bits is not None:
        ctl = got["control_widest_gap"]
        out["control"] = {
            "correct": check.verdict(picked, ctl, rec.compiles, limits),
            "checks": {**checks, "widest_gap": {"value": ctl, "limit": limits["widest_gap"]}}}
    out["checks"] = checks
    return out


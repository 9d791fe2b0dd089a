"""Persistent asymmetric serving runtime: slot table + per-class queues.

The serving-side analogue of the trainer's class-sharded step, and the
direct transplant of the paper's §5.4 insight: workers *keep* their
assignments between micro-kernel grabs instead of re-partitioning the
whole problem every iteration.  The one-shot path (``launch/serve.py
--one-shot``) does the opposite — it re-pads the request batch per the
chunk table on every generate call and replays prompts token-by-token
through per-call jit dispatches, each of which copies the full decode
state.  This engine amortizes all of it:

  * **Fixed pod-major slot table** — ``n_pods × c_max`` decode slots.
    Pod *i* owns the contiguous slot region ``[i·c_max, (i+1)·c_max)``;
    on a multi-class mesh the jitted step runs class-sharded
    (``AsymmetricMesh.class_sharded``), so each pod decodes its region
    under its own class's control tree — two micro-kernel programs in one
    SPMD step, ``ShardProvenance``-proven, exactly as in training.
  * **Paged KV pool** (``paged="auto"|"on"``) — instead of one dense
    ``seq_cap`` KV lane per slot, the engine owns a fixed arena of
    fixed-size pages (:mod:`repro.runtime.paging`) and each slot holds a
    page-index list; memory scales with live tokens.  Pages are reserved
    all-or-nothing at admission (``ceil(min(prompt+max_new, s_cache) /
    page_size)`` — no mid-stream exhaustion; admission defers instead)
    and returned the moment a slot retires, EOS- or budget-stopped.  The
    page size is a per-class tunable defaulting from the classes' tuned
    block configs (min ``bm``), the granularity the paper's §3.3
    configuration step already derived for the memory hierarchy.
  * **Continuous batching** — one admission round takes *mixed-length*
    prompts from every queue head: prompts are right-padded to the round
    maximum and the fused bulk prefill selects each row's logits at its
    own last real token (``plens``), so heterogeneous requests admit in
    a single fused call instead of one round per length.
  * **Per-token EOS stopping** (``eos_id``) — a slot emitting EOS retires
    mid-stream (its pages return to the pool immediately); stats count
    ``completed_eos`` and ``completed_budget`` separately.
  * **Per-class request queues + admission router** — requests are routed
    to a class queue at submit time (largest-remainder over calibrated
    throughput shares, so the split tracks the chunk table), and admitted
    into free slots of that class's region between steps.  Once running,
    a request never moves: steady-state decode performs **zero host
    relayout** (no ``pad_requests``, no chunk-table re-derivation in the
    loop — asserted by tests).
  * **Weights cast once** — the engine serves :func:`serving_params` of
    the caller's tree: the GEMM weights, which every reader casts to
    bfloat16, are cast when the engine is built, never in a step.
  * **Donated decode state** — the slot state (dense lanes or page
    arena) is threaded through the jitted step with ``donate_argnums``,
    so the caches update in place instead of being copied every token.
  * **Fused bulk prefill** — one jitted program consumes the whole
    (padded) prompt batch and writes the admitted slots' cache lanes,
    bit-identical to the token-by-token replay.  The paged engine
    prefills *in place* through the same page tables it decodes through
    (donated arena; busy slots' rows are pointed at phantom pages so
    their live pages cannot be touched).
  * **Rebalance hysteresis** — per-pod step timings feed
    ``DynamicScheduler.observe``; slot-region budgets re-derive *only*
    past the scheduler's drift threshold, and only between steps.

Exactness contract (tested in tests/test_paged_serving.py): the paged
engine's tokens are **bit-identical** to the dense slot-table engine's
for the same requests.  Free-but-refreshed lanes decode the same pad
streams as the dense engine's phantom rows through *phantom pages*: one
shared lane per pod, since every row of the decode step is row-local
(MoE layers route dropless, so identical pad rows get identical
results).  Retired-but-not-refreshed lanes are marked
dead via the ``live`` mask in *both* engines (their attention output is
zeroed, making their rows cache-independent), which is what lets the
paged engine free a retired slot's pages immediately without its dead
lane diverging from the dense engine's.
"""

from __future__ import annotations

import collections
import dataclasses
import inspect
import time
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import ArchConfig
from repro.core.asymmetric import AsymmetricMesh
from repro.core.schedule import deficit_route
from repro.distributed import sharding as SH
from repro.models import layers as L
from repro.models import model_zoo as Z
from repro.models import transformer as TX
from repro.observability import compiles
from repro.observability import metrics as MET
from repro.observability import trace as T
from repro.runtime.paging import PagePool, PageSpec, SENTINEL, divisor_page_size

_M = None


def _metrics():
    """Engine metric families, registered once on first enabled use."""

    global _M
    if _M is None:
        _M = {
            "queue_depth": MET.gauge(
                "engine_queue_depth", "Requests waiting per class queue",
                labels=("device_class",)),
            "slot_occupancy": MET.gauge(
                "engine_slot_occupancy", "Active decode slots per pod",
                labels=("pod",)),
            "admissions": MET.counter(
                "engine_admissions_total", "Requests admitted into slots",
                labels=("device_class",)),
            "tokens": MET.counter(
                "engine_tokens_total", "Tokens generated by decode steps"),
            "step_seconds": MET.histogram(
                "engine_decode_step_seconds", "Decode step wall time"),
            "queue_wait": MET.histogram(
                "engine_queue_wait_seconds",
                "Submit-to-admission wait of each admitted request",
                labels=("device_class",)),
            "rebalances": MET.counter(
                "engine_rebalances_total",
                "Slot-budget re-derivations past the drift hysteresis"),
            "kv_pages_free": MET.gauge(
                "engine_kv_pool_pages_free",
                "Unallocated pages in the KV page pool"),
            "kv_pages_live": MET.gauge(
                "engine_kv_pool_pages_live",
                "Allocated pages in the KV page pool"),
            "page_allocs": MET.counter(
                "engine_page_allocs_total",
                "KV pages allocated at admission",
                labels=("device_class",)),
        }
    return _M


def _moe_counters():
    """The MoE routing counters.  Counted whether or not tracing is on: the
    counts come back with every MoE step's tokens, and the benchmark's
    step-FLOPs reader takes the routed share from them."""

    return (MET.counter("engine_moe_routed_tokens_total",
                        "Decode tokens of busy slots routed to each held expert, "
                        "summed over the MoE layers", labels=("expert",)),
            MET.counter("engine_moe_step_tokens_total",
                        "Decode tokens of busy slots in MoE decode steps"))


# Modeled wall seconds for one slot-row of decode work on a pod of unit
# aggregate throughput (``rel_throughput × chips_per_pod == 1``).  The
# absolute scale is arbitrary — only ratios between pods matter for the
# modeled throughput — but a fixed constant keeps the figures
# deterministic across hosts (unlike wall clocks).
MODELED_ROW_S = 1e-3


def _hook_takes_units(hook) -> bool:
    """Does a pod_time_hook accept ``(step, pod_units)`` (new style) or
    just ``(step)`` (the legacy test-lambda signature)?"""

    try:
        sig = inspect.signature(hook)
    except (TypeError, ValueError):
        return False
    n = 0
    for p in sig.parameters.values():
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
            n += 1
        elif p.kind == p.VAR_POSITIONAL:
            return True
    return n >= 2


def serving_params(params):
    """The weight tree the engine serves from: each
    :data:`~repro.models.layers.GEMM_WEIGHTS` leaf in the compute dtype,
    cast in one jitted call that keeps its sharding; every other leaf, and
    one already in the compute dtype, is the caller's own array.  Each
    reader casts those leaves to the compute dtype before its GEMM, so the
    programs compute the float32 tree's numbers with no weight convert in
    any step.  ``params`` is read, never changed or donated."""

    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    leaves = [w for _, w in flat]
    pick = [i for i, (path, w) in enumerate(flat)
            if getattr(path[-1], "key", None) in L.GEMM_WEIGHTS
            and w.dtype != L.COMPUTE_DTYPE]
    if pick:
        cast = jax.jit(lambda ws: [w.astype(L.COMPUTE_DTYPE) for w in ws])(
            [leaves[i] for i in pick])
        for i, w in zip(pick, cast):
            leaves[i] = w
    return jax.tree.unflatten(tree, leaves)


def weight_bytes(params) -> dict[str, int]:
    """Bytes of ``params`` per dtype name."""

    out = collections.Counter()
    for w in jax.tree.leaves(params):
        out[w.dtype.name] += w.size * w.dtype.itemsize
    return dict(sorted(out.items()))


def _paged_supported(cfg: ArchConfig) -> tuple[bool, str]:
    """Can this arch's decode state page?  (pure KV-cache families only)"""

    if TX.block_kind(cfg) == "mamba":
        return False, "recurrent (Mamba2) state has no KV pages to allocate"
    if cfg.shared_attn_every:
        return False, "the hybrid shared-attention cache is not paged"
    return True, ""


@dataclasses.dataclass(frozen=True)
class Request:
    """One queued generation request."""

    rid: int
    prompt: np.ndarray        # (P,) int32
    max_new_tokens: int
    submitted: float = 0.0    # perf_counter at submit


@dataclasses.dataclass
class Completion:
    """A finished request: prompt + generated tokens, and where it ran."""

    rid: int
    tokens: np.ndarray        # (P + n_generated,) int32
    prompt_len: int
    slot: int                 # global slot id (pod-major)
    pod: int
    device_class: str
    stop: str = "budget"      # "budget" | "eos"


@dataclasses.dataclass
class EngineStats:
    """Timing/behavior counters (compiles split out of the call times)."""

    compiles: int = 0             # programs compiled or cache-loaded inside engine calls
    compile_s: float = 0.0        # seconds those compiles and loads took
    prefill_s: float = 0.0        # bulk prefill seconds, compiles excluded
    decode_s: float = 0.0         # decode step seconds, compiles excluded
    decode_steps: int = 0         # steps counted in decode_s
    tokens: int = 0               # tokens generated by those steps
    admitted: int = 0
    completed: int = 0
    completed_eos: int = 0        # retired by emitting eos_id
    completed_budget: int = 0     # retired by exhausting max_new_tokens
    admission_rounds: int = 0
    admission_deferrals: int = 0  # admissions deferred by page-pool exhaustion
    # Host relayouts performed by the decode loop.  Structurally zero: the
    # engine has no relayout site after admission (requests keep their
    # slot), which tests/test_serving.py enforces by *poisoning*
    # pad_requests / chunk_table / batch_layout and running the loop — the
    # counter exists for the JSON reporting contract, not as the guard.
    host_relayouts: int = 0
    rebalances: int = 0           # slot-budget re-derivations past hysteresis
    # Decode seconds on the modeled clock (:data:`MODELED_ROW_S`, not wall
    # clock); deterministic across hosts.
    modeled_decode_s: float = 0.0
    # Bytes of the served weight tree per dtype (``engine_weight_bytes``).
    weight_bytes: dict = dataclasses.field(default_factory=dict)

    @property
    def tokens_per_s(self) -> float:
        """Decode throughput, compiles excluded."""

        return self.tokens / self.decode_s if self.decode_s > 0 else 0.0

    @property
    def modeled_tokens_per_s(self) -> float:
        """Throughput on the modeled clock (deterministic across hosts)."""

        return self.tokens / self.modeled_decode_s if self.modeled_decode_s > 0 else 0.0

    def snapshot(self) -> dict:
        """Every counter plus the derived throughput, JSON-serializable —
        the one reporting surface (serve.py and the metrics snapshot both
        consume this instead of mirroring the field list by hand)."""

        out = dataclasses.asdict(self)
        out["tokens_per_s"] = round(self.tokens_per_s, 3)
        out["modeled_tokens_per_s"] = round(self.modeled_tokens_per_s, 3)
        return out


class ServingEngine:
    """Persistent slot-table serving engine over an :class:`AsymmetricMesh`.

    Parameters
    ----------
    cfg, params : the model (token-in archs only — serving contract).
        The engine serves :func:`serving_params` of ``params``: the
        allow-listed GEMM weights (:data:`repro.models.layers.GEMM_WEIGHTS`)
        cast once to the compute dtype, every other leaf as given.  The
        caller's tree is read, never changed.
    asym : the asymmetric mesh (scheduling state; per-class control trees).
    seq_cap : per-slot cache length (prompt + generation must fit).
    slots_per_pod : ``c_max`` — each pod's fixed slot-region size.
    mesh : jax Mesh with a ``pod`` axis for the class-sharded mixed step;
        built automatically (host mesh) when class_sharded resolves on.
    class_sharded : "auto" | "on" | "off" — as in launch/serve.py.
    donate : donate the decode state through the jitted step (in-place
        cache updates).  Off only for the A/B test of the donation path.
    paged : "off" (default) | "auto" | "on" — replace the dense per-slot
        KV lanes with the paged pool.  "auto" pages every pure KV-cache
        family and silently stays dense where paging is unsupported
        (Mamba2 / hybrid shared-attention state); "on" raises there.
    page_size : tokens per page.  Default: the min tuned ``block.bm``
        across the mesh's classes, rounded down to a divisor of the
        logical cache length (the table width must satisfy
        ``W · page_size == s_cache`` exactly — the bit-identity contract).
    pool_pages : physical pages per pod partition.  Default: enough for
        every slot's full lane plus the phantom lanes (never defers);
        size it below that to trade admission deferrals for memory.
    eos_id : token id that stops a request mid-stream (its slot retires
        and — paged — its pages free immediately).  None disables.
    pod_time_hook : feeds the scheduler's straggler calibration.  The
        default ``"auto"`` installs an inert
        :class:`~repro.observability.probe.StepTimeProbe` (it returns
        ``None`` and the calibration stays frozen, whatever tracing is
        on); pass ``StepTimeProbe(asym, always=True)`` to measure each
        class's real per-row cost.  A callable may take
        ``(step)`` (legacy) or ``(step, pod_units)`` and may return
        ``None`` to skip a step; ``None`` disables the feedback entirely
        — one SPMD step cannot be attributed per pod from the host.
    """

    def __init__(
        self,
        cfg: ArchConfig,
        params,
        asym: AsymmetricMesh,
        *,
        seq_cap: int,
        slots_per_pod: int = 4,
        mesh=None,
        class_sharded: str = "auto",
        donate: bool = True,
        paged: Union[str, bool] = "off",
        page_size: Optional[int] = None,
        pool_pages: Optional[int] = None,
        eos_id: Optional[int] = None,
        pod_time_hook: Union[str, None, Callable[..., Optional[Sequence[float]]]] = "auto",
    ):
        if cfg.embed_inputs or cfg.family == "encdec":
            raise ValueError(f"{cfg.name}: the serving engine targets token-in archs")
        if class_sharded not in ("auto", "on", "off"):
            raise ValueError(f"class_sharded={class_sharded!r}")
        if isinstance(paged, bool):
            paged = "on" if paged else "off"
        if paged not in ("auto", "on", "off"):
            raise ValueError(f"paged={paged!r}")
        self.cfg = cfg
        self.params = serving_params(params)
        self.asym = asym
        self.seq_cap = int(seq_cap)
        self.c_max = int(slots_per_pod)
        self.n_pods = asym.n_pods
        self.n_slots = self.n_pods * self.c_max
        self.donate = bool(donate)
        self.eos_id = None if eos_id is None else int(eos_id)
        if pod_time_hook == "auto":
            from repro.observability.probe import StepTimeProbe

            pod_time_hook = StepTimeProbe(asym)
        self.pod_time_hook = pod_time_hook
        self._hook_takes_units = (
            _hook_takes_units(pod_time_hook) if pod_time_hook is not None else False
        )

        self.mixed = (
            class_sharded != "off"
            and len(asym.classes) > 1
            and jax.device_count() >= asym.n_pods
        )
        if class_sharded == "on" and not self.mixed:
            raise ValueError(
                f"class_sharded='on' needs {asym.n_pods} devices, "
                f"have {jax.device_count()}"
            )
        if self.mixed and mesh is None:
            from repro.launch.mesh import make_host_mesh

            mesh = make_host_mesh(pod=asym.n_pods)
        self.mesh = mesh

        # -- per-class request queues fed by the admission router ----------
        self.queues: list[collections.deque] = [
            collections.deque() for _ in asym.classes
        ]
        self._routed = [0] * len(asym.classes)  # total ever routed per class
        self._next_rid = 0
        self._pod_class = asym.pod_class_indices()

        # -- host-side slot bookkeeping (the device never sees it) ---------
        self.slot_rid = np.full(self.n_slots, -1, np.int64)     # -1 = free
        self.slot_pos = np.zeros(self.n_slots, np.int64)        # next abs position
        self.slot_remaining = np.zeros(self.n_slots, np.int64)
        self._slot_req: dict[int, Request] = {}
        self._slot_toks: dict[int, list[int]] = {}
        self.budgets = [0] * self.n_pods
        self.completions: list[Completion] = []
        self.stats = EngineStats(weight_bytes=weight_bytes(self.params))
        served = MET.gauge("engine_weight_bytes", "Bytes of the served weight tree",
                           labels=("dtype",))
        for dtype, n in self.stats.weight_bytes.items():
            served.labels(dtype=dtype).set(n)
        self._rebalances0 = asym.scheduler.rebalances
        self._pod_agg = [
            asym.class_of_pod(p).rel_throughput * asym.class_of_pod(p).chips_per_pod
            for p in range(self.n_pods)
        ]
        # Lane liveness: True for busy slots and for free lanes refreshed
        # as pad streams at the last admission; False for retired-but-not-
        # refreshed lanes, whose attention output both engines zero.
        self._live = np.zeros(self.n_slots, bool)
        self._pod_of_row = np.arange(self.n_slots) // self.c_max

        # -- KV storage: dense per-slot lanes or the paged pool ------------
        supported, why = _paged_supported(cfg)
        if paged == "on" and not supported:
            raise ValueError(f"paged='on': {cfg.name}: {why}")
        self.paged = paged == "on" or (paged == "auto" and supported)
        self.s_cache = TX.cache_len(cfg, self.seq_cap) if supported else self.seq_cap
        if self.paged:
            if page_size is None:
                page_size = min(t.block.bm for t in asym.control_trees().values())
            ps = divisor_page_size(self.s_cache, page_size)
            w = self.s_cache // ps
            if pool_pages is None:
                pool_pages = (self.c_max + 1) * w   # every lane, plus the phantom
            spec = PageSpec(
                page_size=ps, pages_per_slot=w,
                pages_per_pod=int(pool_pages), n_pods=self.n_pods,
            )
            self.pool: Optional[PagePool] = PagePool(spec, self.c_max)
            self.phantom = self.pool.alloc_phantom()
            self.state = Z.init_decode_state_paged(cfg, spec.n_pages, ps, rows=self.n_slots)
        else:
            self.pool = None
            self.phantom = None
            self.state = Z.init_decode_state(cfg, self.n_slots, self.seq_cap)

        # -- device state: allocated once, donated every step --------------
        self.tokens = jnp.zeros((self.n_slots, 1), jnp.int32)
        self._pos = np.zeros(self.n_slots, np.int64)  # device copy passed per step
        self._step_calls = 0
        self._build()

    # -- compiled programs --------------------------------------------------

    def _build(self):
        cfg, asym = self.cfg, self.asym
        decode = Z.make_decode_fn(cfg)
        if self.paged:
            spec = self.pool.spec
            state_spec = jax.eval_shape(
                lambda: Z.init_decode_state_paged(cfg, spec.n_pages, spec.page_size,
                                                  rows=self.n_slots)
            )
            batch_keys = ("tokens", "page_table", "live")
        else:
            state_spec = Z.decode_state_spec(cfg, self.n_slots, self.seq_cap)
            batch_keys = ("tokens", "live")

        if self.mixed:
            in_specs, out_specs = SH.pod_decode_specs(
                state_spec, batch_keys=batch_keys
            )
            core = asym.class_sharded(
                decode,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=out_specs,
            )
            self.provenance = core.provenance
        else:
            ctx = asym.execution_context()

            def core(params, batch, state, pos):
                with ctx:
                    return decode(params, batch, state, pos)

            self.provenance = None
        self._core = core

        def step_fn(params, batch, state, pos):
            logits, state = core(params, batch, state, pos)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            return nxt, state

        donate = (2,) if self.donate else ()
        self._step = jax.jit(step_fn, donate_argnums=donate)

        bulk = Z.bulk_prefill_from_decode(core)

        if self.paged:
            def prefill_fn(params, batch, state, plens):
                # In-place prefill through the page tables: the arena is
                # donated; busy slots' table rows point at phantom pages,
                # so their live pages flow through untouched.
                pos0 = jnp.zeros((self.n_slots,), jnp.int32)
                logits, state = bulk(params, batch, state, pos0, plens=plens)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
                return nxt, state

            self._prefill = jax.jit(prefill_fn, donate_argnums=donate)
        else:
            def prefill_fn(params, batch, plens):
                # Fresh zero state traced inside the program: the fused
                # prefill writes every admitted lane from scratch in one
                # shot; the merge below keeps busy lanes.
                fresh = Z.init_decode_state(cfg, self.n_slots, self.seq_cap)
                pos0 = jnp.zeros((self.n_slots,), jnp.int32)
                logits, state = bulk(params, batch, fresh, pos0, plens=plens)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
                return nxt, state

            self._prefill = jax.jit(prefill_fn)

        def merge_fn(old_state, new_state, old_tokens, new_tokens, take_new):
            # Lanes in ``take_new`` — the admitted slots plus every free
            # (phantom) lane — take their freshly prefilled lane wholesale
            # (full-row replace: stale cache tails from the previous tenant
            # vanish); busy slots keep their lane bit-for-bit.  Refreshing
            # the phantom lanes keeps them identical to the one-shot padded
            # batch's rows, which MoE capacity routing (cross-row coupling)
            # requires for output bit-identity.  The batch (slot) dim of
            # every state leaf is dim 1.
            def pick(o, n):
                shape = [1] * o.ndim
                shape[1] = o.shape[1]
                return jnp.where(take_new.reshape(shape), n, o)

            state = jax.tree.map(pick, old_state, new_state)
            tokens = jnp.where(take_new[:, None], new_tokens, old_tokens)
            return state, tokens

        self._merge = jax.jit(merge_fn, donate_argnums=(0,) if self.donate else ())

    # -- page-table assembly (paged mode only; host-side, O(B·W)) -----------

    def _localize(self, table: np.ndarray) -> np.ndarray:
        if not self.mixed:
            return table
        return self.pool.localize(table, self._pod_of_row)

    def _step_table(self) -> np.ndarray:
        """The decode step's (B, W) page table: busy slots read their own
        pages, live pad lanes their phantom row, dead lanes SENTINEL
        (writes dropped, reads masked by the zeroed ``live`` output)."""

        busy = self.slot_rid >= 0
        table = self.phantom[self._pod_of_row].copy()
        table[busy] = self.pool.table[busy]
        table[~busy & ~self._live] = SENTINEL
        return self._localize(table)

    # -- admission router ----------------------------------------------------

    def _class_weights(self) -> np.ndarray:
        rates = np.zeros(len(self.asym.classes), np.float64)
        for pod, ci in enumerate(self._pod_class):
            rates[ci] += self.asym.scheduler.rates[pod]
        return rates

    def submit(self, prompt, max_new_tokens: int, *, route_class: Optional[int] = None) -> int:
        """Queue one request; returns its rid.

        The router assigns the request to a class queue by largest
        remainder over the calibrated per-class throughput shares — the
        cumulative routed counts track the chunk table's split, so a batch
        of N submits lands exactly on ``chunk_table(N)`` aggregated by
        class.  ``route_class`` overrides (the batch path routes per an
        explicit layout so it reproduces ``pad_requests`` placement).
        """

        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) + int(max_new_tokens) > self.seq_cap:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new({max_new_tokens}) exceeds "
                f"seq_cap={self.seq_cap}"
            )
        if len(prompt) == 0 or max_new_tokens < 1:
            raise ValueError("need a non-empty prompt and max_new_tokens >= 1")
        rid = self._next_rid
        self._next_rid += 1
        if route_class is None:
            route_class = deficit_route(self._class_weights(), self._routed)
        self.queues[route_class].append(
            Request(rid=rid, prompt=prompt, max_new_tokens=int(max_new_tokens),
                    submitted=time.perf_counter())
        )
        self._routed[route_class] += 1
        return rid

    # -- fleet surface: drain/export, health, calibration --------------------

    def withdraw(self, rid: int) -> Optional[Request]:
        """Remove one *queued* (not yet admitted) request; returns it.

        The router's cumulative count is rolled back so future routing
        reflects only the work the engine kept.  ``None`` if ``rid`` is
        not queued (already admitted, completed, or unknown) — admitted
        work cannot be withdrawn; it runs to completion.
        """

        for ci, q in enumerate(self.queues):
            for i, req in enumerate(q):
                if req.rid == rid:
                    del q[i]
                    self._routed[ci] -= 1
                    return req
        return None

    def export_queued(self) -> list[Request]:
        """Drain every class queue, in submission (rid) order.

        The fleet's migration path: a saturated or dead engine
        hands its not-yet-admitted requests back so they can be re-routed
        elsewhere.  Router counts roll back as in :meth:`withdraw`.
        """

        out: list[Request] = []
        for ci, q in enumerate(self.queues):
            while q:
                out.append(q.popleft())
                self._routed[ci] -= 1
        out.sort(key=lambda r: r.rid)
        return out

    def partial_tokens(self, rid: int) -> Optional[np.ndarray]:
        """Tokens generated so far for an in-flight request (else None).

        The fleet's streaming surface: completed tokens come from
        :attr:`completions`; mid-decode progress comes from here.
        """

        for slot, req in self._slot_req.items():
            if req.rid == rid:
                return np.asarray(self._slot_toks[slot], np.int32)
        return None

    def calibrated_tps(self) -> float:
        """Aggregate calibrated throughput (sum of per-pod EMA rates).

        Dimensionless rows-per-modeled-second units — exactly what the
        fleet scheduler needs as this engine's ``rel_throughput``.
        """

        return float(np.sum(self.asym.scheduler.rates))

    def health(self) -> dict:
        """The engine health surface a fleet front polls each tick."""

        return {
            "queued": sum(len(q) for q in self.queues),
            "active": int((self.slot_rid >= 0).sum()),
            "slots": self.n_slots,
            "calibrated_tps": self.calibrated_tps(),
            "completed": self.stats.completed,
            "admission_deferrals": self.stats.admission_deferrals,
        }

    # -- slot-region budgets (resize between steps only) ---------------------

    def _refresh_budgets(self):
        old_budgets = list(self.budgets)
        old_count = self.stats.rebalances
        n_work = int((self.slot_rid >= 0).sum()) + sum(len(q) for q in self.queues)
        self.budgets = self.asym.slot_budgets(self.c_max, n_work)
        # The scheduler re-derives its table (counting a rebalance) only
        # past the hysteresis threshold — whether the trigger was a budget
        # refresh or the batch path's routing table.
        self.stats.rebalances = self.asym.scheduler.rebalances - self._rebalances0
        if T.enabled() and self.stats.rebalances > old_count:
            _metrics()["rebalances"].inc(self.stats.rebalances - old_count)
            T.instant(
                "engine.rebalance", cat="engine",
                before=old_budgets, after=list(self.budgets),
                n_work=n_work, drift=self.asym.scheduler.drift(),
                rebalances=self.stats.rebalances,
            )

    def _pod_active(self) -> list[int]:
        act = (self.slot_rid >= 0).reshape(self.n_pods, self.c_max)
        return [int(a.sum()) for a in act]

    def _free_slot(self, pod: int) -> Optional[int]:
        if self._pod_active()[pod] >= self.budgets[pod]:
            return None
        return self._any_free_slot(pod)

    def _any_free_slot(self, pod: int) -> Optional[int]:
        lo = pod * self.c_max
        for s in range(lo, lo + self.c_max):
            if self.slot_rid[s] < 0:
                return s
        return None

    # -- admission (bulk prefill into free slots) -----------------------------

    def admit(self) -> int:
        """Admit queued requests into free budgeted slots; returns count.

        Continuous batching: one round takes *mixed-length* prompts from
        every queue head — right-padded to the round maximum, each row's
        first generated token selected at its own last real prompt token
        (``plens``).  The fused prefill runs over the full slot table
        (free lanes see zero prompts — the same phantom rows the one-shot
        padded batch carries).  In paged mode every page a request can
        ever touch is reserved all-or-nothing first; a pod partition that
        cannot cover the head request defers it (FIFO) untouched.
        """

        mark = compiles.read()
        with T.span("engine.admit", cat="engine") as sp:
            with T.span("engine.admit.route", cat="engine"):
                self._refresh_budgets()
                busy_before = self.slot_rid >= 0
                if not any(self.queues):
                    return 0
                batch = self._take(budgeted=True)
                if not batch and not busy_before.any():
                    # Starvation guard: a queue whose class drew a zero
                    # budget at low load must still make progress when
                    # nothing is running (the scheduler's starvation
                    # floor, at admission granularity).
                    batch = self._take(budgeted=False)
                if not batch:
                    return 0
                now = time.perf_counter()
                waits = [now - req.submitted for _, req in batch]
                rp = max(len(req.prompt) for _, req in batch)
                sp.tag(admitted=len(batch), round_len=rp,
                       rids=[req.rid for _, req in batch],
                       queue_wait_max_s=max(waits))

            t0 = time.perf_counter()
            with T.span("engine.admit.inputs", cat="engine"):
                prompts = np.zeros((self.n_slots, rp), np.int32)
                plens = np.full(self.n_slots, rp, np.int32)
                for slot, req in batch:
                    prompts[slot, : len(req.prompt)] = req.prompt
                    plens[slot] = len(req.prompt)
                # Admitted slots plus every phantom (free) lane take the
                # fresh prefill — see merge_fn.
                take_new = ~busy_before
                pbatch = {"tokens": jnp.asarray(prompts),
                          "live": jnp.ones((self.n_slots,), bool)}
                if self.pool is not None:
                    table = self.phantom[self._pod_of_row].copy()
                    for slot, _ in batch:
                        table[slot] = self.pool.table[slot]
                    pbatch["page_table"] = jnp.asarray(self._localize(table))
                plens_dev = jnp.asarray(plens)
            with T.span("engine.admit.launch", cat="engine"):
                if self.pool is not None:
                    nxt, self.state = self._prefill(
                        self.params, pbatch, self.state, plens_dev
                    )
                    self.tokens = jnp.where(
                        jnp.asarray(take_new)[:, None], nxt, self.tokens
                    )
                else:
                    nxt, fresh_state = self._prefill(self.params, pbatch, plens_dev)
                    self.state, self.tokens = self._merge(
                        self.state, fresh_state, self.tokens, nxt,
                        jnp.asarray(take_new),
                    )
            with T.span("engine.admit.wait", cat="engine"):
                first = np.asarray(nxt)  # blocks; first generated token per lane
            with T.span("engine.admit.retire", cat="engine"):
                dt = time.perf_counter() - t0
                n_compiled, compile_s = compiles.since(mark)
                sp.tag(compiles=n_compiled)
                self.stats.compiles += n_compiled
                self.stats.compile_s += compile_s
                self.stats.prefill_s += dt - compile_s
                if T.enabled():
                    self._record_admit_metrics(batch, waits)

                self._live[take_new] = True
                self._pos[take_new] = plens[take_new]
                for slot, req in batch:
                    self.slot_pos[slot] = len(req.prompt)
                    self._slot_req[slot] = req
                    self._slot_toks[slot] = [int(first[slot, 0])]
                    self.slot_remaining[slot] = req.max_new_tokens - 1
                    self.stats.admitted += 1
                    if self.eos_id is not None and int(first[slot, 0]) == self.eos_id:
                        self._retire(slot, stop="eos")
                    elif self.slot_remaining[slot] == 0:
                        self._retire(slot, stop="budget")
                self.stats.admission_rounds += 1
        return len(batch)

    def _take(self, budgeted: bool) -> list[tuple[int, Request]]:
        """Take queue heads into free slots (budgeted or any), reserving
        each slot (and, paged, its pages) as it goes."""

        out = []
        for ci, q in enumerate(self.queues):
            pods = [p for p, c in enumerate(self._pod_class) if c == ci]
            while q:
                req = q[0]
                slot = None
                for pod in pods:
                    slot = (
                        self._free_slot(pod)
                        if budgeted
                        else self._any_free_slot(pod)
                    )
                    if slot is not None:
                        break
                if slot is None:
                    break
                if self.pool is not None:
                    need = min(
                        len(req.prompt) + req.max_new_tokens, self.s_cache
                    )
                    if not self.pool.alloc(slot, need):
                        # Pod partition exhausted: defer the head (it
                        # keeps its FIFO turn; the pool and every live
                        # slot are untouched — all-or-nothing alloc).
                        self.stats.admission_deferrals += 1
                        break
                    self._note_page_alloc(slot, need)
                q.popleft()
                out.append((slot, req))
                self.slot_rid[slot] = req.rid  # reserve before next _free_slot
        return out

    def _retire(self, slot: int, stop: str = "budget"):
        req = self._slot_req.pop(slot)
        pod = slot // self.c_max
        self.completions.append(
            Completion(
                rid=req.rid,
                tokens=np.concatenate(
                    [req.prompt, np.asarray(self._slot_toks.pop(slot), np.int32)]
                ),
                prompt_len=len(req.prompt),
                slot=slot,
                pod=pod,
                device_class=self.asym.class_of_pod(pod).name,
                stop=stop,
            )
        )
        self.slot_rid[slot] = -1
        self.slot_remaining[slot] = 0
        self._live[slot] = False
        self.stats.completed += 1
        if stop == "eos":
            self.stats.completed_eos += 1
        else:
            self.stats.completed_budget += 1
        if self.pool is not None:
            freed = self.pool.free_slot(slot)
            if T.enabled() and freed:
                m = _metrics()
                m["kv_pages_free"].set(self.pool.pages_free)
                m["kv_pages_live"].set(self.pool.pages_live)
                T.instant(
                    "engine.page_free", cat="engine", slot=slot, pages=freed,
                    stop=stop, pages_live=self.pool.pages_live,
                    pages_free=self.pool.pages_free,
                )

    def _note_page_alloc(self, slot: int, n_tokens: int):
        if not T.enabled():
            return
        m = _metrics()
        pages = self.pool.spec.pages_for(n_tokens)
        name = self.asym.class_of_pod(slot // self.c_max).name
        m["page_allocs"].labels(device_class=name).inc(pages)
        m["kv_pages_free"].set(self.pool.pages_free)
        m["kv_pages_live"].set(self.pool.pages_live)
        T.instant(
            "engine.page_alloc", cat="engine", slot=slot, pages=pages,
            pages_live=self.pool.pages_live, pages_free=self.pool.pages_free,
        )

    # -- steady-state decode ---------------------------------------------------

    def step(self) -> int:
        """One decode step over the whole slot table; returns active count.

        No host relayout: the step consumes the resident token/position
        vectors, the lane-liveness mask, (paged) the page table assembled
        from pool state, and the donated slot state.  Every slot advances
        (freed slots as phantom rows), matching the one-shot padded batch
        program exactly.
        """

        active = self.slot_rid >= 0
        n_active = int(active.sum())
        if n_active == 0:
            return 0
        units = self._pod_active_before(active)
        mark = compiles.read()
        t0 = time.perf_counter()
        with T.span("engine.step", cat="engine", step=self._step_calls,
                    active=n_active, rows=self.n_slots) as sp:
            with T.span("engine.step.inputs", cat="engine"):
                batch, pos = self.step_inputs()
            with T.span("engine.step.launch", cat="engine"):
                nxt, self.state = self._step(self.params, batch, self.state, pos)
            with T.span("engine.step.wait", cat="engine"):
                # blocks: the step's wall time is real
                if "moe_routed" in self.state:
                    toks, routed = jax.device_get((nxt, self.state["moe_routed"]))
                else:
                    toks, routed = np.asarray(nxt), None
            with T.span("engine.step.retire", cat="engine"):
                if routed is not None:
                    self._note_routed(sp, routed, active)
                self.tokens = nxt
                dt = time.perf_counter() - t0
                n_compiled, compile_s = compiles.since(mark)
                sp.tag(compiles=n_compiled)
                self.stats.compiles += n_compiled
                self.stats.compile_s += compile_s
                self.stats.decode_s += dt - compile_s
                self.stats.decode_steps += 1
                self.stats.tokens += n_active
                self._advance_modeled_clock(units)
                self._step_calls += 1
                self._pos += 1  # every slot ages (phantom rows match one-shot padding)

                for slot in np.nonzero(active)[0]:
                    slot = int(slot)
                    tok = int(toks[slot, 0])
                    self._slot_toks[slot].append(tok)
                    self.slot_remaining[slot] -= 1
                    if self.eos_id is not None and tok == self.eos_id:
                        self._retire(slot, stop="eos")
                    elif self.slot_remaining[slot] == 0:
                        self._retire(slot, stop="budget")

                if T.enabled():
                    self._record_step_metrics(dt, n_active, units)

            # Straggler feedback: per-pod timings re-calibrate the scheduler
            # (budgets only re-derive at admission, past hysteresis).  One
            # SPMD step yields one wall time, not per-pod times — without a
            # hook there is no per-pod signal, and fabricating equal times
            # would read occupancy as speed and erode the calibrated ratios
            # (at full occupancy every pod shows the same units/dt), so the
            # calibration comes only from a hook (the default StepTimeProbe
            # is inert; one built with ``always=True`` measures each class's
            # real per-row cost).
            with T.span("engine.step.calibrate", cat="engine"):
                if self.pod_time_hook is not None:
                    times = (
                        self.pod_time_hook(self._step_calls - 1, units)
                        if self._hook_takes_units
                        else self.pod_time_hook(self._step_calls - 1)
                    )
                    if times is not None:
                        self.asym.observe_step(units, list(times))
        return n_active

    def _note_routed(self, sp, routed: np.ndarray, active: np.ndarray):
        """The step's held-expert routing over busy slots: per MoE layer and
        expert (``routed`` is (n_moe_layers, n_slots, n_experts)).  The
        counts feed the ``engine.step`` span's ``moe_routed_local`` and
        ``moe_max_expert_load`` and the :func:`_moe_counters`."""

        load = routed[:, active].sum(axis=1)        # (n_moe_layers, n_experts)
        sp.tag(moe_routed_local=int(load.sum()), moe_max_expert_load=int(load.max()))
        per_expert, tokens = _moe_counters()
        first = self.cfg.moe.first_expert
        for e, n in enumerate(load.sum(axis=0)):
            per_expert.labels(expert=str(first + e)).inc(int(n))
        tokens.inc(int(active.sum()))

    def step_inputs(self) -> tuple[dict, jnp.ndarray]:
        """``(batch, pos)`` of the next decode step: the resident tokens,
        the lane-liveness mask, (paged) the page table, and the per-slot
        positions."""

        batch = {"tokens": self.tokens, "live": jnp.asarray(self._live)}
        if self.pool is not None:
            batch["page_table"] = jnp.asarray(self._step_table())
        return batch, jnp.asarray(self._pos, jnp.int32)

    def step_logits(self) -> jnp.ndarray:
        """``(n_slots, 1, vocab)`` logits of the next decode step, computed
        by the engine's own step program without advancing or donating
        the state."""

        batch, pos = self.step_inputs()
        core = self._core
        return jax.jit(lambda p, b, s, q: core(p, b, s, q)[0])(
            self.params, batch, self.state, pos
        )

    def _pod_active_before(self, active_mask: np.ndarray) -> list[int]:
        act = active_mask.reshape(self.n_pods, self.c_max)
        return [int(a.sum()) for a in act]

    def _advance_modeled_clock(self, units: Sequence[int]):
        """Advance ``stats.modeled_decode_s`` by one decode step: the
        slowest pod's row count over its aggregate throughput (×
        :data:`MODELED_ROW_S` — the SPMD barrier means every pod waits for
        the straggler).  Deterministic — no wall clocks."""

        self.stats.modeled_decode_s += MODELED_ROW_S * max(
            (u / agg for u, agg in zip(units, self._pod_agg) if agg > 0),
            default=0.0,
        )

    # -- KV memory accounting ---------------------------------------------------

    def kv_stats(self) -> dict:
        """KV memory accounting for reporting (serve.py / bench_serving).

        Dense mode reports the lanes' actual byte size.  Paged mode adds
        the pool occupancy counters and the headline comparison:
        ``peak_kv_bytes`` (peak live pages × bytes per page — the arena an
        operator could have provisioned) vs ``dense_kv_bytes`` (what the
        dense engine allocates for the same slot table).
        """

        caches = {k: v for k, v in self.state.items() if k != "moe_routed"}
        arena = int(sum(x.nbytes for x in jax.tree.leaves(caches)))
        if self.pool is None:
            return {"paged": False, "kv_bytes": arena}
        spec = self.pool.spec
        # Bytes of one token's cache in every layer: K and V rows, or the
        # latent row (MLA).
        tok_bytes = arena // (spec.n_pages * spec.page_size)
        page_bytes = tok_bytes * spec.page_size
        return {
            "paged": True,
            "page_size": spec.page_size,
            "pages_per_slot": spec.pages_per_slot,
            "n_pages": spec.n_pages,
            "pages_live": self.pool.pages_live,
            "pages_free": self.pool.pages_free,
            "peak_live_pages": self.pool.peak_live,
            "phantom_pages": int(self.phantom.size),
            "page_bytes": page_bytes,
            "peak_kv_bytes": self.pool.peak_live * page_bytes,
            "arena_kv_bytes": arena,
            "dense_kv_bytes": tok_bytes * self.n_slots * self.s_cache,
        }

    # -- metrics (every method below only runs while tracing is enabled) ------

    def _record_step_metrics(self, dt, n_active, per_pod):
        m = _metrics()
        for ci, c in enumerate(self.asym.classes):
            m["queue_depth"].labels(device_class=c.name).set(len(self.queues[ci]))
        for pod, occ in enumerate(per_pod):
            m["slot_occupancy"].labels(pod=str(pod)).set(occ)
        m["tokens"].inc(n_active)
        m["step_seconds"].observe(dt)

    def _record_admit_metrics(self, batch, waits):
        m = _metrics()
        for (slot, _), wait in zip(batch, waits):
            name = self.asym.class_of_pod(slot // self.c_max).name
            m["admissions"].labels(device_class=name).inc()
            m["queue_wait"].labels(device_class=name).observe(wait)
        for ci, c in enumerate(self.asym.classes):
            m["queue_depth"].labels(device_class=c.name).set(len(self.queues[ci]))

    # -- driver ----------------------------------------------------------------

    def run(self, *, max_steps: Optional[int] = None) -> list[Completion]:
        """Admit + decode until queues and slots drain.

        Returns the completions produced by *this* call (the cumulative
        history stays available as ``self.completions``).
        """

        start = len(self.completions)
        steps = 0
        while True:
            if any(self.queues):
                admitted = self.admit()
                if admitted == 0 and not (self.slot_rid >= 0).any():
                    raise RuntimeError(
                        "admission made no progress with an empty slot table "
                        "(a queued request's page reservation exceeds its pod's "
                        "pool partition?)"
                    )
            if not (self.slot_rid >= 0).any():
                break
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return self.completions[start:]

    def generate(self, prompts: np.ndarray, gen_len: int) -> np.ndarray:
        """Batch convenience: decode ``prompts`` (B, P) for ``gen_len`` tokens.

        Routes per the scheduler's chunk table in request order —
        reproducing exactly the ``pad_requests`` pod-major placement of
        the one-shot path, which is what makes the outputs bit-identical
        to it (same slot layout, same phantom rows).  Returns
        ``(B, P + gen_len)`` tokens in submission order (rows of requests
        stopped early by ``eos_id`` are zero-padded past their last
        token).
        """

        prompts = np.asarray(prompts, np.int32)
        n = prompts.shape[0]
        sizes = self.asym.chunk_table(n).sizes()
        rid_of = {}
        pos = 0
        for pod, size in enumerate(sizes):
            ci = self._pod_class[pod]
            for r in range(pos, pos + size):
                rid_of[self.submit(prompts[r], gen_len, route_class=ci)] = r
            pos += size
        done = self.run()
        out = np.zeros((n, prompts.shape[1] + gen_len), np.int32)
        for c in done:
            if c.rid in rid_of:
                out[rid_of[c.rid], : len(c.tokens)] = c.tokens
        return out


__all__ = ["ServingEngine", "Request", "Completion", "EngineStats", "serving_params",
           "weight_bytes"]

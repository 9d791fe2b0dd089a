"""Class-routed execution contexts: one ambient control tree per device class.

The paper's central mechanism (Section 5.3) is that *every* micro-kernel
invocation runs under the executing core class's control tree — the tree
picks both the blocking parameters and the micro-kernel implementation.
This module is the jax_pallas realization of that routing:

  * :class:`ExecutionContext` — a context-manager binding one device
    class's :class:`~repro.core.control_tree.ControlTree` as the *ambient*
    configuration.  Every :func:`repro.kernels.ops.gemm` /
    :func:`~repro.kernels.ops.linear` call anywhere in the model zoo
    resolves its backend and block shapes from the active context instead
    of per-call arguments, so model code never hand-threads
    ``config=``/``backend=``.
  * the **backend dispatch table** (:data:`BACKENDS`) — the single
    vocabulary of micro-kernel implementations (previously scattered
    across ``ops.py``'s if/elif chain, ``control_tree.py``'s ``Backend``
    literal, and the ``_on_tpu()`` auto-probe).
  * :func:`resolve_block_config` — the single tuned-or-analytical
    resolution path: the ``$REPRO_TUNING_CACHE`` entry for the class's
    core spec wins, the Section-3.3 analytical derivation is the fallback.
  * :func:`class_sharded` — per-class programs within one SPMD step: a
    ``shard_map`` over the pod axis in which each pod shard runs the
    program traced under *its* class's context (true CA-SAS, paper
    §5.3–5.4; DESIGN.md §2), with :class:`ShardProvenance` recording
    which tree governs which shard.

With **no context active** every call behaves exactly as before this layer
existed: ``backend="auto"`` probes the JAX backend (Pallas on TPU, XLA
otherwise) and ``config=None`` resolves via the env-var cache keyed by
``$REPRO_TUNING_SPEC`` — bit-identical defaults.

Contexts nest: entering a context shadows the outer one, exiting restores
it (exception-safe).  All state lives in :mod:`contextvars` (the active
context plus a per-thread/per-task token stack), so one shared context
object may be entered concurrently from several threads or asyncio tasks
— enter/exit just have to pair up locally, as with any context manager.
Explicit per-call arguments always win over the ambient context — the
context only fills ``backend="auto"`` and ``config=None`` holes.
"""

from __future__ import annotations

import contextvars
import dataclasses
from typing import TYPE_CHECKING, Callable, Literal, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.blocking import TPU_V5E, BlockConfig, TpuCoreSpec, derive_block_config
from repro.observability import trace as _obs

if TYPE_CHECKING:  # control_tree imports Backend from here; keep it one-way.
    from repro.core.control_tree import ControlTree

# ---------------------------------------------------------------------------
# Backend dispatch table (the one backend vocabulary)
# ---------------------------------------------------------------------------

Backend = Literal[
    "xla", "pallas", "pallas_interpret", "pallas_lean", "pallas_lean_interpret"
]


def _xla_gemm(a2, b, config, out_dtype):
    # Declare the dot output in the compute dtype: the MXU still
    # accumulates fp32 per shard, but GSPMD then places the
    # tensor-parallel all-reduce on the bf16 tensor instead of an fp32
    # intermediate — half the wire bytes on every row-parallel
    # projection (EXPERIMENTS.md §Perf A).
    pet = jnp.float32 if out_dtype == jnp.float32 else out_dtype
    return jnp.dot(a2, b, preferred_element_type=pet).astype(out_dtype)


def _pallas_gemm(a2, b, config, out_dtype):
    from repro.kernels.gemm import gemm_pallas

    return gemm_pallas(a2, b, config, out_dtype=out_dtype)


def _pallas_interpret_gemm(a2, b, config, out_dtype):
    from repro.kernels.gemm import gemm_pallas

    return gemm_pallas(a2, b, config, out_dtype=out_dtype, interpret=True)


def _pallas_lean_gemm(a2, b, config, out_dtype):
    from repro.kernels.gemm import gemm_pallas_lean

    return gemm_pallas_lean(a2, b, config, out_dtype=out_dtype)


def _pallas_lean_interpret_gemm(a2, b, config, out_dtype):
    from repro.kernels.gemm import gemm_pallas_lean

    return gemm_pallas_lean(a2, b, config, out_dtype=out_dtype, interpret=True)


def _paged_attn_xla(q, pages_k, pages_v, page_table, pos, layer):
    from repro.kernels.paged_attention import paged_attention_xla

    return paged_attention_xla(q, pages_k, pages_v, page_table, pos, layer)


def _paged_attn_pallas(q, pages_k, pages_v, page_table, pos, layer):
    from repro.kernels.paged_attention import paged_attention_pallas

    return paged_attention_pallas(q, pages_k, pages_v, page_table, pos, layer)


def _paged_attn_pallas_interpret(q, pages_k, pages_v, page_table, pos, layer):
    from repro.kernels.paged_attention import paged_attention_pallas

    return paged_attention_pallas(
        q, pages_k, pages_v, page_table, pos, layer, interpret=True
    )


# name -> kernel callable.  The keys are the only backend names the stack
# accepts; ``"auto"`` is a request resolved by :func:`resolve_backend` /
# :func:`resolve_paged_attn_backend`, never a table entry.  Entries span
# more than one *op family* now (GEMM micro-kernels take
# ``(a2, b, config, out_dtype)``; paged-attention decode kernels take
# ``(q, pages_k, pages_v, page_table, pos, layer)``) — :data:`BACKEND_OPS` tags
# each name with its family and the dispatch funnels validate the tag, so
# a tree or CLI flag can never route a GEMM into an attention kernel.
BACKENDS: dict[str, Callable] = {
    "xla": _xla_gemm,
    "pallas": _pallas_gemm,
    "pallas_interpret": _pallas_interpret_gemm,
    "pallas_lean": _pallas_lean_gemm,
    "pallas_lean_interpret": _pallas_lean_interpret_gemm,
    "paged_attn_xla": _paged_attn_xla,
    "paged_attn_pallas": _paged_attn_pallas,
    "paged_attn_pallas_interpret": _paged_attn_pallas_interpret,
}

# name -> op family ("gemm" | "paged_attn").
BACKEND_OPS: dict[str, str] = {
    "xla": "gemm",
    "pallas": "gemm",
    "pallas_interpret": "gemm",
    "pallas_lean": "gemm",
    "pallas_lean_interpret": "gemm",
    "paged_attn_xla": "paged_attn",
    "paged_attn_pallas": "paged_attn",
    "paged_attn_pallas_interpret": "paged_attn",
}

BACKEND_NAMES: tuple[str, ...] = tuple(BACKENDS)

# The GEMM sub-vocabulary — what control trees, the tuner, and the
# ``--backend`` CLI flags may name.
GEMM_BACKEND_NAMES: tuple[str, ...] = tuple(
    n for n, op in BACKEND_OPS.items() if op == "gemm"
)


def backend_op(name: str) -> str:
    """The op family of a dispatch-table entry (validating the name)."""

    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}")
    return BACKEND_OPS[name]

# Compiled backend -> its CPU-runnable interpret twin (identity for
# backends that already run anywhere).  The parity harness walks BACKENDS
# through this map, so every new table entry MUST be registered here —
# tests/test_backend_parity.py fails loudly on a missing twin.
INTERPRET_TWIN: dict[str, str] = {
    "xla": "xla",
    "pallas": "pallas_interpret",
    "pallas_interpret": "pallas_interpret",
    "pallas_lean": "pallas_lean_interpret",
    "pallas_lean_interpret": "pallas_lean_interpret",
    "paged_attn_xla": "paged_attn_xla",
    "paged_attn_pallas": "paged_attn_pallas_interpret",
    "paged_attn_pallas_interpret": "paged_attn_pallas_interpret",
}

# Pipelined backend -> the VMEM-lean variant of the same execution family
# (compiled or interpret).  Control trees use this to keep a class's full
# shared panel when only the lean working set fits its VMEM.
LEAN_VARIANTS: dict[str, str] = {
    "pallas": "pallas_lean",
    "pallas_interpret": "pallas_lean_interpret",
}

# Backends whose kernels stage inputs double-buffered; the lean variants
# single-buffer (BlockConfig.vmem_bytes(double_buffer=False) is their
# working-set model).  "xla" ignores block configs entirely.
_LEAN_BACKENDS = frozenset(LEAN_VARIANTS.values())


def interpret_twin(name: str) -> str:
    """The CPU-runnable twin of a backend (validating both names)."""

    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}")
    twin = INTERPRET_TWIN.get(name)
    if twin is None or twin not in BACKENDS:
        raise ValueError(
            f"backend {name!r} has no interpret twin registered in "
            f"INTERPRET_TWIN — add one so the parity harness can cover it"
        )
    return twin


def backend_double_buffers(name: str) -> bool:
    """Does this backend's kernel stage inputs double-buffered?

    Decides which VMEM working-set model governs block-config feasibility
    (``BlockConfig.fits(spec, double_buffer=...)``).
    """

    return name not in _LEAN_BACKENDS


# interpret name -> its compiled family (inverse of INTERPRET_TWIN,
# identity pairs dropped): "pallas_lean_interpret" -> "pallas_lean".
_COMPILED_TWIN: dict[str, str] = {
    t: c for c, t in INTERPRET_TWIN.items() if c != t
}


def align_backend_family(variant: str, requested: str) -> str:
    """Map a recorded kernel variant onto ``requested``'s execution family.

    A tuning-cache entry normally records the *hardware* variant
    (``"pallas_lean"``); when the tree is built for interpret-mode
    execution the same variant must run through its interpret twin — and,
    symmetrically, an interpret name that leaked into a cache (hand-edited
    or merged from a CPU run) must map back to the compiled kernel on a
    hardware tree rather than silently running the Python interpreter.
    """

    if requested.endswith("_interpret"):
        return interpret_twin(variant)
    return _COMPILED_TWIN.get(variant, variant)


def backend_vocabulary() -> frozenset[str]:
    """Every backend token the stack accepts anywhere: the dispatch-table
    names plus the ``"auto"`` request.  The static analyzer's drift
    detector (RPR005) is keyed off this, so the lint vocabulary can never
    diverge from the live registry."""

    return frozenset(BACKENDS) | {"auto"}


def validate_registry() -> list[str]:
    """Statically verify the dispatch tables' closure invariants.

    Returns a list of human-readable violations (empty == healthy).  Ran
    by the ``repro.analysis`` registry pass and by a fast unit test, so a
    new backend that forgets its twin/family registration fails at
    import-check time instead of deep inside dispatch.  Checks:

    * ``BACKENDS`` and ``BACKEND_OPS`` name exactly the same entries, and
      every op-family tag is known;
    * ``INTERPRET_TWIN`` covers every entry, maps into the table, keeps
      the op family, and is idempotent (a twin is its own twin) — the
      parity harness walks this map, so these are its route guarantees;
    * ``LEAN_VARIANTS`` maps double-buffered entries to single-buffered
      entries of the same family;
    * ``kernels.gemm.GEMM_KERNELS`` (the tuner's search dimension) names
      only compiled GEMM-family dispatch entries.
    """

    problems: list[str] = []
    known_ops = {"gemm", "paged_attn"}
    if set(BACKENDS) != set(BACKEND_OPS):
        problems.append(
            f"BACKENDS/BACKEND_OPS disagree: "
            f"{sorted(set(BACKENDS) ^ set(BACKEND_OPS))}"
        )
    for name, op in BACKEND_OPS.items():
        if op not in known_ops:
            problems.append(f"BACKEND_OPS[{name!r}] = {op!r} is not a known op family")
    if set(INTERPRET_TWIN) != set(BACKENDS):
        problems.append(
            f"INTERPRET_TWIN does not cover BACKENDS exactly: "
            f"{sorted(set(INTERPRET_TWIN) ^ set(BACKENDS))}"
        )
    for name, twin in INTERPRET_TWIN.items():
        if twin not in BACKENDS:
            problems.append(f"INTERPRET_TWIN[{name!r}] = {twin!r} not in BACKENDS")
            continue
        if BACKEND_OPS.get(name) != BACKEND_OPS.get(twin):
            problems.append(
                f"INTERPRET_TWIN[{name!r}] = {twin!r} crosses op families"
            )
        if INTERPRET_TWIN.get(twin) != twin:
            problems.append(
                f"interpret twin {twin!r} (of {name!r}) is not its own twin"
            )
    for name, lean in LEAN_VARIANTS.items():
        if name not in BACKENDS or lean not in BACKENDS:
            problems.append(f"LEAN_VARIANTS {name!r} -> {lean!r} not in BACKENDS")
            continue
        if BACKEND_OPS[name] != BACKEND_OPS[lean]:
            problems.append(
                f"LEAN_VARIANTS {name!r} -> {lean!r} crosses op families"
            )
        if not backend_double_buffers(name) or backend_double_buffers(lean):
            problems.append(
                f"LEAN_VARIANTS {name!r} -> {lean!r} must map a "
                "double-buffered entry to a single-buffered one"
            )
    from repro.kernels.gemm import GEMM_KERNELS

    for name in GEMM_KERNELS:
        if name not in BACKENDS:
            problems.append(f"GEMM_KERNELS entry {name!r} not in BACKENDS")
        elif BACKEND_OPS[name] != "gemm":
            problems.append(f"GEMM_KERNELS entry {name!r} is not a GEMM backend")
        elif name.endswith("_interpret"):
            problems.append(
                f"GEMM_KERNELS entry {name!r} is an interpret twin — the "
                "variant registry holds compiled kernels only"
            )
    return problems


def on_tpu() -> bool:
    """The auto-probe: is the default JAX backend a TPU?"""

    return jax.default_backend() == "tpu"


def resolve_backend(name: str) -> str:
    """Collapse a GEMM ``"auto"`` to a concrete table entry; validate the rest.

    GEMM callers only (control trees, the ops funnel, dry-run): a name
    from another op family is rejected here, at resolution time, so it can
    never reach a kernel with the wrong signature.
    """

    if name == "auto":
        return "pallas" if on_tpu() else "xla"
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}")
    if BACKEND_OPS[name] != "gemm":
        raise ValueError(
            f"backend {name!r} is a {BACKEND_OPS[name]!r} kernel, not a GEMM"
        )
    return name


def resolve_paged_attn_backend(name: str) -> str:
    """Collapse a paged-attention ``"auto"``; validate the op family."""

    if name == "auto":
        return "paged_attn_pallas" if on_tpu() else "paged_attn_xla"
    if backend_op(name) != "paged_attn":
        raise ValueError(
            f"backend {name!r} is a {BACKEND_OPS[name]!r} kernel, not a "
            f"paged-attention kernel"
        )
    return name


def dispatch_gemm(a2, b, *, config=None, backend: str = "auto", out_dtype=None):
    """Route a 2-D GEMM through the backend table (the kernels' funnel)."""

    out_dtype = out_dtype or a2.dtype
    return BACKENDS[resolve_backend(backend)](a2, b, config, out_dtype)


def dispatch_paged_attention(
    q, pages_k, pages_v, page_table, pos, layer, *, backend: str = "auto"
):
    """Route a paged decode-attention call through the backend table.

    The decode path's funnel: ``layers.decode_attention_paged`` calls this
    per layer with the stacked arenas and the layer to read, so the paged
    kernels live in the same vocabulary — and the same parity harness — as
    the GEMM micro-kernels.  ``"auto"`` takes the active context's
    ``paged_attn`` entry.
    """

    if backend == "auto" and current_context() is not None:
        backend = current_context().paged_attn
    return BACKENDS[resolve_paged_attn_backend(backend)](
        q, pages_k, pages_v, page_table, pos, layer
    )


def dispatch_paged_mla(q_c, q_pe, pages_c, pages_pe, page_table, pos, layer, *,
                       scale: float, backend: str = "auto"):
    """Route a paged latent-attention decode call (``layers.decode_mla_paged``)
    to the latent twin of the resolved paged-attention backend: the XLA
    gather, the Pallas kernel, or its interpreter."""

    from repro.kernels import paged_attention as PA

    if backend == "auto" and current_context() is not None:
        backend = current_context().paged_attn
    fn = {
        "paged_attn_xla": PA.paged_mla_xla,
        "paged_attn_pallas": PA.paged_mla_pallas,
        "paged_attn_pallas_interpret": PA.paged_mla_pallas_interpret,
    }[resolve_paged_attn_backend(backend)]
    return fn(q_c, q_pe, pages_c, pages_pe, page_table, pos, layer, scale=scale)


# ---------------------------------------------------------------------------
# Block-config resolution (tuned cache -> analytical fallback)
# ---------------------------------------------------------------------------

_DTYPE_NAMES = {1: "int8", 2: "bfloat16", 4: "float32"}


def dtype_name_for_bytes(dtype_bytes: int) -> str:
    return _DTYPE_NAMES.get(dtype_bytes, f"bytes{dtype_bytes}")


def tuned_block_config(
    m: int,
    k: int,
    n: int,
    *,
    spec: Optional[TpuCoreSpec] = None,
    dtype_name: str = "bfloat16",
    dtype_bytes: int = 2,
) -> Optional[BlockConfig]:
    """The ``$REPRO_TUNING_CACHE`` entry for this (spec, dtype, shape), or None.

    ``spec=None`` keeps today's kernel-path behavior: the cache key's spec
    name comes from ``$REPRO_TUNING_SPEC`` (default ``tpu-v5e``).
    """

    from repro.tuning.cache import cached_block_config

    return cached_block_config(
        m, k, n, dtype_name, dtype_bytes,
        spec_name=spec.name if spec is not None else None,
    )


def tuned_kernel_backend(
    m: int,
    k: int,
    n: int,
    *,
    spec: Optional[TpuCoreSpec] = None,
    dtype_name: str = "bfloat16",
) -> Optional[str]:
    """The kernel variant the tuner recorded for this entry, or None.

    The cache entry's ``"backend"`` field holds the winning micro-kernel
    variant (a :data:`BACKENDS` key) since the variant search landed;
    older caches stored the *measurement* backend there (``"cost-model"``/
    ``"wallclock"``) — any value outside the dispatch table is ignored, so
    old caches keep working with the default kernel.
    """

    from repro.tuning.cache import cached_kernel_backend

    name = cached_kernel_backend(
        m, k, n, dtype_name, spec_name=spec.name if spec is not None else None
    )
    return name if name in BACKENDS else None


def resolve_block_config(
    m: int,
    k: int,
    n: int,
    *,
    spec: Optional[TpuCoreSpec] = None,
    dtype_name: str = "bfloat16",
    dtype_bytes: int = 2,
    double_buffer: bool = True,
) -> tuple[BlockConfig, str]:
    """Tuned config on cache hit, analytical derivation on miss.

    Returns ``(config, source)`` with ``source in ("tuned", "analytical")``
    so callers (control trees, tests) can record provenance.
    ``double_buffer`` names the *consuming kernel's* buffering model: the
    analytical fallback derives under it, and a tuned hit is honored only
    if the consumer can hold it — an entry recorded for the lean kernel
    (or one that overflows the spec double-buffered) must not reach the
    pipelined kernel, whose working set is twice the one the entry was
    validated under.  (The converse is safe: any double-buffer-feasible
    block is lean-feasible.)
    """

    cfg = tuned_block_config(
        m, k, n, spec=spec, dtype_name=dtype_name, dtype_bytes=dtype_bytes
    )
    if cfg is not None:
        usable = True
        if double_buffer:
            recorded = tuned_kernel_backend(
                m, k, n, spec=spec, dtype_name=dtype_name
            )
            if recorded is not None and not backend_double_buffers(recorded):
                usable = False  # a lean-only winner: pipelined would spill
            elif spec is not None and not cfg.fits(spec):
                usable = False
        if usable:
            return cfg, "tuned"
    return (
        derive_block_config(
            m, k, n,
            spec=spec or TPU_V5E,
            dtype_bytes=dtype_bytes,
            double_buffer=double_buffer,
        ),
        "analytical",
    )


# ---------------------------------------------------------------------------
# The execution context itself
# ---------------------------------------------------------------------------


def _same_bucket(a: tuple[int, int, int], b: tuple[int, int, int]) -> bool:
    """Do two problem shapes pad to the same 128-lane MXU tile per dim?

    Uses the tuning cache's own bucket function so block-config reuse
    decisions can never drift from the cache-key bucketing.
    """

    from repro.tuning.cache import _bucket

    return all(_bucket(x) == _bucket(y) for x, y in zip(a, b))


_ACTIVE: contextvars.ContextVar[Optional["ExecutionContext"]] = contextvars.ContextVar(
    "repro_execution_context", default=None
)
# LIFO of reset tokens for the enters made *in the current thread/task*.
# Held in a ContextVar of immutable tuples: each asyncio task (copied
# context) and each thread sees its own stack, so a single shared
# ExecutionContext instance can be entered concurrently everywhere —
# enter/exit only have to pair up locally, as with any context manager.
_TOKENS: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_execution_tokens", default=()
)


@dataclasses.dataclass
class ExecutionContext:
    """Ambient per-device-class execution configuration (a context manager).

    Binds one class's control tree: ``ops.gemm`` calls under this context
    take their backend from ``tree.backend`` and, for Pallas backends,
    resolve their block shapes per call shape from the tuning cache keyed
    by ``tree.spec`` (falling back to the analytical derivation for that
    spec).  ``tree.block`` itself is the canonical-shape config carrying
    the Section-5.3 shared-panel structure; per-call shapes re-resolve so
    a little-VMEM class never inherits a big-class block it cannot hold.
    Paged decode attention under this context runs ``paged_attn``
    (``"auto"``: the platform's kernel).
    """

    device_class: str
    tree: "ControlTree"
    paged_attn: str = "auto"

    def __enter__(self) -> "ExecutionContext":
        # Token bookkeeping lives in _TOKENS (per-thread *and* per-task),
        # never on the instance: one long-lived context (e.g. a Trainer's)
        # may be entered concurrently from threads and asyncio tasks.
        token = _ACTIVE.set(self)
        _TOKENS.set(_TOKENS.get() + (token,))
        return self

    def __exit__(self, *exc) -> bool:
        stack = _TOKENS.get()
        _TOKENS.set(stack[:-1])
        _ACTIVE.reset(stack[-1])
        return False

    @property
    def spec(self) -> TpuCoreSpec:
        return self.tree.spec

    def backend(self) -> str:
        """The concrete dispatch-table entry this context routes to."""

        return resolve_backend(self.tree.backend)

    def paged_attn_backend(self) -> str:
        """The paged-attention entry this context routes to."""

        return resolve_paged_attn_backend(self.paged_attn)

    def block_config(
        self, m: int, k: int, n: int, dtype_name: str, dtype_bytes: int
    ) -> BlockConfig:
        """Per-call-shape block config for this class (tuned or analytical).

        ``tree.block`` carries either a hand-picked configuration (trees
        built directly, no ``problem_shape`` recorded) or the Section-5.3
        shared-panel constraint, neither of which a fresh per-spec
        derivation can reconstruct — so it is reused whenever it can be.

        Hand-built trees are authoritative (the old ``gemm_with_tree``
        semantics): their block is used verbatim on a dtype match, or with
        the operand bytes re-labelled otherwise (same shapes), with a
        fresh derivation only if the re-labelled working set overflows
        this class's VMEM.

        Mesh-built trees reuse ``tree.block`` for calls padding into the
        same 128-lane bucket the tree was built for.  Resolution order:
        tree.block on a dtype match; else a tuned cache entry for this
        class's spec at the call's actual dtype — under a Loop-3 (rows)
        tree only if it agrees on the shared ``bk``, the same rule
        ``build_control_trees`` enforces; else the dtype-re-labelled
        tree.block (VMEM-fit guarded).  Off-bucket shapes re-resolve
        against this class's spec.

        VMEM-fit checks use the *tree backend's* buffering model: a lean
        (single-buffered) backend admits blocks the pipelined kernel could
        not hold — that is the point of the variant.  A tuned entry is
        likewise honored only if this tree's kernel can hold it (a
        lean-only winner must not reach a pipelined tree).  Hand-built
        blocks are clamped to the lane-padded call dims — they apply to
        *every* call shape, and an un-clamped oversize block would now be
        rejected by the kernels' shape validation instead of silently
        padding.
        """

        tree = self.tree
        db = backend_double_buffers(self.backend())
        hand_built = tree.problem_shape is None

        def _clamp(blk: BlockConfig) -> BlockConfig:
            lane = tree.spec.lane
            pad = lambda d: max(lane, ((d + lane - 1) // lane) * lane)  # noqa: E731
            return dataclasses.replace(
                blk,
                bm=min(blk.bm, pad(m)),
                bk=min(blk.bk, pad(k)),
                bn=min(blk.bn, pad(n)),
            )

        reuse = hand_built or _same_bucket((m, k, n), tree.problem_shape)
        if reuse and tree.block.dtype_bytes == dtype_bytes:
            return _clamp(tree.block) if hand_built else tree.block
        if reuse:
            relabeled = dataclasses.replace(tree.block, dtype_bytes=dtype_bytes)
            if hand_built and relabeled.fits(tree.spec, double_buffer=db):
                return _clamp(relabeled)
        tuned = tuned_block_config(
            m, k, n, spec=tree.spec, dtype_name=dtype_name, dtype_bytes=dtype_bytes
        )
        if (
            tuned is not None
            and (not reuse or tree.coarse_loop != "rows" or tuned.bk == tree.block.bk)
            and tuned.fits(tree.spec, double_buffer=db)
        ):
            return tuned
        if reuse and not hand_built and relabeled.fits(tree.spec, double_buffer=db):
            return relabeled
        return derive_block_config(
            m, k, n, spec=tree.spec, dtype_bytes=dtype_bytes, double_buffer=db
        )


def current_context() -> Optional[ExecutionContext]:
    """The innermost active context, or None (→ pre-context defaults)."""

    return _ACTIVE.get()


# ---------------------------------------------------------------------------
# Per-class programs within one SPMD step (shard_map over the pod axis)
# ---------------------------------------------------------------------------


def unchecked_shard_map(f, *, mesh, in_specs, out_specs, auto=frozenset()):
    """``jax.shard_map`` with the varying-manual-axes check (``check_vma``) off.

    Class-sharded bodies carry per-shard control flow the checker cannot
    see through, so it is always disabled here.  ``auto`` names the mesh
    axes left to GSPMD; the body is manual over every other axis.
    """

    manual = frozenset(mesh.axis_names) - frozenset(auto)
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=manual, check_vma=False,
    )


@dataclasses.dataclass(frozen=True)
class ShardProvenance:
    """Which class's control tree governs one pod shard (paper §5.3)."""

    pod: int
    device_class: str
    spec: str
    backend: str
    block_source: str  # "tuned" | "analytical" — the tree's provenance
    block: BlockConfig


@dataclasses.dataclass(eq=False)  # identity hash/eq: jit-able as a callable
class ClassShardedFn:
    """A callable wrapping ``fn`` so each pod shard runs its own class's
    program, plus the per-shard provenance (for assertions / telemetry).

    ``trace_log`` records, at trace time, which contexts actually traced a
    branch — the proof that each class's tree was ambient while its
    program was built (appended once per trace; jit retraces append again).
    """

    fn: Callable
    provenance: tuple[ShardProvenance, ...]
    trace_log: list
    mixed: bool  # False on the single-class fallback (no shard_map)

    def __call__(self, *args):
        return self.fn(*args)


def class_sharded(
    fn: Callable,
    *,
    mesh,
    contexts: Sequence[ExecutionContext],
    pod_class: Sequence[int],
    in_specs,
    out_specs,
    axis: str = "pod",
    epilogue: Optional[Callable] = None,
    auto: Optional[frozenset] = None,
    pod_class_spec=None,
) -> ClassShardedFn:
    """True CA-SAS within one SPMD step: per-class programs under shard_map.

    The paper's §5.3/§5.4 schemes run *different* control trees on the big
    and LITTLE clusters simultaneously inside one gemm.  Here, a
    ``shard_map`` over the mesh's ``axis`` (the pod axis) gives every pod
    its shard of the work, and each shard *selects the program traced
    under its own class's execution context*: ``fn`` is traced once per
    class, each trace under that class's :class:`ExecutionContext` (so
    every ``ops.gemm`` in branch *c* resolves class *c*'s tuned block
    config and backend), and a ``lax.switch`` on the shard's class index
    picks the branch at run time.  Pods of the same class take the same
    branch, so intra-class (auto-axis) collectives stay consistent.

    ``contexts`` is ordered by class index; ``pod_class[i]`` is the class
    index of pod ``i`` and ``pod_class_spec`` shards it one-per-pod —
    ``repro.distributed.sharding.pod_class_specs`` produces the pair
    (``AsymmetricMesh.class_sharded`` feeds it through; the spec defaults
    to ``P(axis)``).  The class index reaches each shard as a pod-sharded
    *input*, not ``axis_index`` — keeping the body free of partition-id
    lowering so partial-auto meshes work on every backend.

    ``epilogue(out, shard_args, axis)`` runs inside the shard_map body
    *after* the switch — the one place cross-pod collectives are legal
    (all pods execute it, branch-independent).  Use it for the weighted
    gradient psum of a train step.  With a single class the fallback
    wrapper simply activates the one context around ``fn`` — no
    shard_map, bit-identical to the pre-class-sharded path — and calls
    ``epilogue`` with ``axis=None``.

    ``fn`` must itself contain no cross-``axis`` collectives (they would
    run under a data-dependent branch and deadlock across classes).

    The shard_map is **fully manual** by default: devices sharing a pod
    coordinate replicate that pod's program (exact, and free when the
    non-pod axes have extent 1 — the host realization).  Passing the
    non-pod axes via ``auto`` (the body is then manual only over the
    others, ``jax.shard_map``'s ``axis_names``) would let GSPMD keep
    partitioning the fine-grain Loop-4 math across them.  XLA's
    partitioner CHECK-failed on ``lax.scan`` inside a ``switch`` branch
    under a manual subgroup with jax 0.4.x; with jax 0.9 a toy
    scan-in-switch compiles on the CPU, but no zoo model has been tried
    that way — so ``auto`` stays opt-in.
    """

    contexts = list(contexts)
    if not contexts:
        raise ValueError("need at least one execution context")
    pod_class = tuple(int(c) for c in pod_class)
    if any(c < 0 or c >= len(contexts) for c in pod_class):
        raise ValueError(
            f"pod_class {pod_class} out of range for {len(contexts)} classes"
        )
    provenance = tuple(
        ShardProvenance(
            pod=i,
            device_class=contexts[c].device_class,
            spec=contexts[c].spec.name,
            backend=contexts[c].backend(),
            block_source=contexts[c].tree.block_source,
            block=contexts[c].tree.block,
        )
        for i, c in enumerate(pod_class)
    )
    trace_log: list = []

    if len(contexts) == 1:
        # Single-class fallback: the one context governs the whole program
        # — exactly the pre-class-sharded execution path, no shard_map.
        ctx = contexts[0]

        def single(*args):
            with ctx:
                trace_log.append((ctx.device_class, ctx.tree.block_source))
                _obs.instant(
                    "execution.trace", cat="execution", mixed=False,
                    device_class=ctx.device_class, backend=ctx.backend(),
                    block_source=ctx.tree.block_source,
                )
                out = fn(*args)
            if epilogue is not None:
                out = epilogue(out, args, None)
            return out

        return ClassShardedFn(
            fn=single, provenance=provenance, trace_log=trace_log, mixed=False
        )

    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {axis!r} axis; axes={mesh.axis_names}")
    if mesh.shape[axis] != len(pod_class):
        raise ValueError(
            f"pod_class covers {len(pod_class)} pods but mesh axis "
            f"{axis!r} has size {mesh.shape[axis]}"
        )
    if auto is None:
        auto = frozenset()
    manual = frozenset(mesh.axis_names) - frozenset(auto)

    def _branch(ctx: ExecutionContext):
        def branch(ops):
            with ctx:
                # Trace-time record: this class's tree was ambient while
                # its per-class program was built.
                trace_log.append((ctx.device_class, ctx.tree.block_source))
                _obs.instant(
                    "execution.trace", cat="execution", mixed=True,
                    device_class=ctx.device_class, backend=ctx.backend(),
                    block_source=ctx.tree.block_source,
                )
                return fn(*ops)

        return branch

    branches = [_branch(ctx) for ctx in contexts]

    def body(cls, *shard_args):
        from repro.distributed.sharding import activation_manual_axes

        # Manual axes are fixed inside this body: activation constraints
        # traced here must not mention them.
        with activation_manual_axes(manual):
            out = jax.lax.switch(cls[0], branches, shard_args)
            if epilogue is not None:
                out = epilogue(out, shard_args, axis)
        return out

    from jax.sharding import PartitionSpec as P

    if pod_class_spec is None:
        pod_class_spec = P(axis)
    smap = unchecked_shard_map(
        body,
        mesh=mesh,
        in_specs=(pod_class_spec,) + tuple(in_specs),
        out_specs=out_specs,
        auto=auto,
    )
    idx = jnp.asarray(pod_class, jnp.int32)

    def wrapped(*args):
        return smap(idx, *args)

    return ClassShardedFn(
        fn=wrapped, provenance=provenance, trace_log=trace_log, mixed=True
    )


def context_for_tree(tree: "ControlTree") -> ExecutionContext:
    """Wrap an existing control tree (e.g. one of ``build_control_trees``)."""

    return ExecutionContext(device_class=tree.device_class, tree=tree)


def default_context(
    *,
    spec: Optional[TpuCoreSpec] = None,
    shape: tuple[int, int, int] = (1024, 1024, 1024),
    backend: str = "auto",
    device_class: Optional[str] = None,
    paged_attn: str = "auto",
) -> ExecutionContext:
    """A single-class context for homogeneous runs (dry-run, plain serving).

    With no tuning cache active this is behavior-neutral: the tree holds
    the analytical config and the auto-resolved backend, exactly what a
    bare ``ops.gemm`` call would pick.
    """

    from repro.core.control_tree import build_control_trees

    spec = spec or TPU_V5E
    name = device_class or spec.name
    trees = build_control_trees(
        {name: spec}, *shape, backend=resolve_backend(backend)
    )
    return ExecutionContext(device_class=name, tree=trees[name], paged_attn=paged_attn)


__all__ = [
    "Backend",
    "BACKENDS",
    "BACKEND_NAMES",
    "BACKEND_OPS",
    "GEMM_BACKEND_NAMES",
    "INTERPRET_TWIN",
    "LEAN_VARIANTS",
    "ClassShardedFn",
    "ExecutionContext",
    "ShardProvenance",
    "align_backend_family",
    "backend_double_buffers",
    "backend_op",
    "backend_vocabulary",
    "validate_registry",
    "class_sharded",
    "context_for_tree",
    "current_context",
    "default_context",
    "dispatch_gemm",
    "dispatch_paged_attention",
    "dtype_name_for_bytes",
    "interpret_twin",
    "on_tpu",
    "resolve_backend",
    "resolve_block_config",
    "resolve_paged_attn_backend",
    "tuned_block_config",
    "tuned_kernel_backend",
    "unchecked_shard_map",
]

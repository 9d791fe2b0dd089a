"""Config/artifact contracts: tuning caches, shipped trees, bench JSONs.

* **RPR201 — block-config contracts.**  Every committed tuning-cache
  entry and every control tree buildable from the shipped core specs must
  satisfy, *under the buffering model of the kernel that will consume it*
  (single-buffer for ``pallas_lean``-family variants, double otherwise):

    - the VMEM working set fits the named spec's budget,
    - all block dims are 128-lane aligned,
    - no block dim exceeds the lane-padded problem it was recorded for
      (the PR-4 bug class: an oversized ``bk`` silently multiplies padded
      FLOPs — ``kernels.gemm.validate_block_config`` now raises at call
      time; this check catches the bad entry at commit time),
    - cache keys bucket consistently with the recorded shape,
    - under the Loop-3 (rows) coarse loop, all classes of a tree family
      share one ``bk`` (the shared-B-panel constraint of §5.3).

* **RPR202 — bench artifact schema.**  ``artifacts/bench/BENCH_*.json``
  must be ``{"meta": {...}, "records": [...]}`` as written by
  ``benchmarks.harness.write_json`` — the CI baseline comparison and the
  perf-trajectory tooling both parse exactly that shape.

Nothing here executes a kernel: caches are parsed, trees are *built*
(pure Python derivation), artifacts are schema-checked.
"""

from __future__ import annotations

import json
import os
import re
from typing import Optional

from repro.analysis.diagnostics import Diagnostic

_KEY_RE = re.compile(r"^(?P<spec>[^/]+)/(?P<dtype>[^/]+)/(?P<m>\d+)x(?P<k>\d+)x(?P<n>\d+)$")

# Required provenance keys of a harness ``meta`` block.
_META_KEYS = ("git_sha", "jax_version", "timestamp")


def looks_like_tuning_cache(payload: object) -> bool:
    return (
        isinstance(payload, dict)
        and "entries" in payload
        and "version" in payload
        and isinstance(payload.get("entries"), dict)
    )


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def check_tuning_cache_file(path: str) -> list[Diagnostic]:
    """Validate one tuning-cache JSON against the block-config contracts."""

    from repro.core.blocking import BlockConfig
    from repro.core.execution import BACKENDS, backend_double_buffers
    from repro.kernels.gemm import LANE
    from repro.tuning.cache import CACHE_VERSION, shape_bucket_key
    from repro.tuning.candidates import SPECS

    diags: list[Diagnostic] = []

    def bad(msg: str) -> None:
        diags.append(Diagnostic(code="RPR201", path=path, line=1, message=msg))

    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        bad(f"unreadable tuning cache: {e}")
        return diags
    if not looks_like_tuning_cache(payload):
        return diags  # not a cache; nothing to assert
    if payload.get("version") != CACHE_VERSION:
        # Version-mismatched caches are invalidated wholesale at load time
        # (by design), so their entries carry no contract to verify.
        return diags

    for key, entry in payload["entries"].items():
        m = _KEY_RE.match(key)
        if m is None:
            bad(f"entry key {key!r} is not spec/dtype/MxKxN")
            continue
        spec_name = m.group("spec")
        spec = SPECS.get(spec_name)
        if spec is None:
            bad(
                f"entry {key!r} names unknown core spec {spec_name!r} "
                f"(known: {sorted(SPECS)})"
            )
            continue
        try:
            cfg = BlockConfig(
                bm=int(entry["bm"]),
                bk=int(entry["bk"]),
                bn=int(entry["bn"]),
                dtype_bytes=int(entry.get("dtype_bytes", 2)),
                acc_bytes=int(entry.get("acc_bytes", 4)),
            )
        except (KeyError, TypeError, ValueError) as e:
            bad(f"entry {key!r} malformed: {e}")
            continue

        for dim_name, blk in (("bm", cfg.bm), ("bk", cfg.bk), ("bn", cfg.bn)):
            if blk % LANE != 0 or blk < LANE:
                bad(
                    f"entry {key!r}: {dim_name}={blk} is not "
                    f"{LANE}-lane aligned"
                )

        backend = entry.get("backend")
        db = (
            backend_double_buffers(backend)
            if isinstance(backend, str) and backend in BACKENDS
            else True
        )
        if not cfg.fits(spec, double_buffer=db):
            model = "double" if db else "single"
            bad(
                f"entry {key!r}: working set "
                f"{cfg.vmem_bytes(double_buffer=db)} B ({model}-buffered, "
                f"backend={backend!r}) exceeds {spec_name}'s VMEM budget "
                f"{int(spec.vmem_bytes * spec.vmem_fill)} B"
            )

        shape = entry.get("shape")
        if (
            isinstance(shape, (list, tuple))
            and len(shape) == 3
            and all(isinstance(d, int) and d > 0 for d in shape)
        ):
            sm, sk, sn = shape
            for dim_name, dim, blk in (
                ("bm", sm, cfg.bm), ("bk", sk, cfg.bk), ("bn", sn, cfg.bn)
            ):
                padded = max(LANE, _round_up(dim, LANE))
                if blk > padded:
                    axis = {"bm": "M", "bk": "K", "bn": "N"}[dim_name]
                    bad(
                        f"entry {key!r}: {dim_name}={blk} exceeds the "
                        f"lane-padded {axis}={padded} of its recorded shape "
                        f"{sm}x{sk}x{sn} — padded-FLOPs multiplier "
                        "(the PR-4 bug class)"
                    )
            expect = shape_bucket_key(
                spec_name, m.group("dtype"), sm, sk, sn
            )
            if expect != key:
                bad(
                    f"entry {key!r}: recorded shape {sm}x{sk}x{sn} buckets "
                    f"to {expect!r} — key and shape drifted apart"
                )
    return diags


def check_shipped_trees(
    shapes: Optional[list[tuple[int, int, int]]] = None,
) -> list[Diagnostic]:
    """Build control trees from the shipped specs; verify their contracts.

    Every ``BlockConfig`` reachable from the registered spec family
    (``tuning.candidates.SPECS``) through :func:`build_control_trees`
    must fit its class's VMEM under the tree backend's buffering model,
    stay lane-aligned, and honor the shared-``bk`` constraint when the
    coarse loop shares the B panel.
    """

    from repro.core.control_tree import build_control_trees
    from repro.core.execution import backend_double_buffers
    from repro.kernels.gemm import LANE
    from repro.tuning.candidates import SPECS

    anchor = "src/repro/core/control_tree.py"
    diags: list[Diagnostic] = []
    shapes = shapes or [(1024, 1024, 1024), (2048, 2048, 2048), (512, 4096, 512)]
    for m, k, n in shapes:
        for backend in ("xla", "pallas"):
            for coarse_loop in ("rows", "cols"):
                trees = build_control_trees(
                    dict(SPECS), m, k, n,
                    backend=backend, coarse_loop=coarse_loop,
                    use_cache=False,
                )
                bks = set()
                for name, tree in trees.items():
                    where = (
                        f"tree[{name}] ({m}x{k}x{n}, backend={backend}, "
                        f"coarse={coarse_loop})"
                    )
                    db = backend_double_buffers(tree.backend)
                    if not tree.block.fits(tree.spec, double_buffer=db):
                        diags.append(
                            Diagnostic(
                                code="RPR201", path=anchor, line=1,
                                message=(
                                    f"{where}: block {tree.block.bm}x"
                                    f"{tree.block.bk}x{tree.block.bn} "
                                    f"overflows {tree.spec.name} VMEM under "
                                    f"its {'double' if db else 'single'}-"
                                    "buffered model"
                                ),
                            )
                        )
                    for blk in (tree.block.bm, tree.block.bk, tree.block.bn):
                        if blk % LANE != 0:
                            diags.append(
                                Diagnostic(
                                    code="RPR201", path=anchor, line=1,
                                    message=(
                                        f"{where}: block dim {blk} is not "
                                        f"{LANE}-lane aligned"
                                    ),
                                )
                            )
                    bks.add(tree.block.bk)
                if coarse_loop == "rows" and len(bks) > 1:
                    diags.append(
                        Diagnostic(
                            code="RPR201", path=anchor, line=1,
                            message=(
                                f"shared-B-panel violation at {m}x{k}x{n} "
                                f"(backend={backend}): classes disagree on "
                                f"the shared bk: {sorted(bks)}"
                            ),
                        )
                    )
    return diags


def check_bench_artifact(path: str) -> list[Diagnostic]:
    """Schema-check one ``BENCH_*.json`` against the harness contract."""

    diags: list[Diagnostic] = []

    def bad(msg: str) -> None:
        diags.append(Diagnostic(code="RPR202", path=path, line=1, message=msg))

    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        bad(f"unreadable bench artifact: {e}")
        return diags
    if not isinstance(payload, dict):
        bad(f"top level must be an object, got {type(payload).__name__}")
        return diags
    meta = payload.get("meta")
    records = payload.get("records")
    if not isinstance(meta, dict):
        bad("missing/non-object `meta` block (harness.write_json stamps it)")
    else:
        missing = [k for k in _META_KEYS if k not in meta]
        if missing:
            bad(f"meta block missing provenance keys: {missing}")
    if not isinstance(records, list):
        bad("missing/non-list `records`")
    elif not all(isinstance(r, dict) for r in records):
        bad("every record must be an object")
    return diags


def check_artifacts_dir(art_dir: str) -> list[Diagnostic]:
    diags: list[Diagnostic] = []
    if not os.path.isdir(art_dir):
        return diags
    for fname in sorted(os.listdir(art_dir)):
        if fname.startswith("BENCH_") and fname.endswith(".json"):
            diags.extend(check_bench_artifact(os.path.join(art_dir, fname)))
    return diags


__all__ = [
    "check_tuning_cache_file",
    "check_shipped_trees",
    "check_bench_artifact",
    "check_artifacts_dir",
    "looks_like_tuning_cache",
]

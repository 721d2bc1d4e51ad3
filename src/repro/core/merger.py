"""Tensor merger: rebuild logical full tensors from shards (paper §4.1, §4.4).

Given rank-local shards plus the annotation-derived shard mapping, the merger

* reassembles the logical full tensor;
* verifies coverage — **no overlap, no omission** of any element;
* verifies **replica consistency**: shards from ranks that map to identical
  slices (e.g. main gradients across DP ranks when ZeRO is off) must agree;
  a disagreement is reported as a *conflicting tensor* (the classic missing
  all-reduce signature, paper §4.4).

``merge_jax_array`` additionally cross-checks a ``jax.Array``'s actual device
layout against the user's annotation, catching "the framework sharded this
differently than you told me" bugs before any value comparison happens.

``merge_microbatch_traces`` is the **per-rank trace path** (paper Fig 5):
given the stage-local, per-microbatch traces a real pipeline schedule emits,
it concatenates the microbatch axis, canonicalizes stage-local layer names
via the per-stage ``stage_layer_table`` renaming, accumulates per-microbatch
parameter-gradient contributions, and verifies (stage, microbatch) coverage —
no microbatch contributed twice, none missing — before any value comparison.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

from repro.core.annotations import ShardSpec, slices_for_rank

# relative tolerance for replica agreement: replicas are produced by the SAME
# reduction on each rank, so they should match to ~machine epsilon.
REPLICA_RTOL = 1e-5


@dataclass
class MergeReport:
    ok: bool = True
    conflicts: list = field(default_factory=list)   # replica disagreements
    overlap: int = 0
    omission: int = 0
    layout_mismatches: list = field(default_factory=list)
    rank_problems: list = field(default_factory=list)  # per-rank trace merge

    def problems(self) -> list[str]:
        out = []
        if self.overlap:
            out.append(f"{self.overlap} elements covered more than once")
        if self.omission:
            out.append(f"{self.omission} elements not covered by any shard")
        for c in self.conflicts:
            out.append(f"replica conflict at coords {c['coords']} vs "
                       f"{c['ref_coords']}: rel_err={c['rel_err']:.3e}")
        for m in self.layout_mismatches:
            out.append(f"layout mismatch at coords {m['coords']}: annotation "
                       f"says {m['expected']}, array is {m['actual']}")
        out.extend(self.rank_problems)
        return out


def merge_shards(shards: dict[tuple, np.ndarray], spec: ShardSpec,
                 sizes: dict[str, int], global_shape: tuple[int, ...],
                 replica_rtol: float = REPLICA_RTOL
                 ) -> tuple[np.ndarray, MergeReport]:
    """shards: {coords tuple (in AXES order of `sizes` keys) -> local array}.

    ``sizes`` maps axis name -> degree; coords tuples are keyed in the same
    order as ``sizes``.
    """
    axes = list(sizes)
    report = MergeReport()
    full = np.zeros(global_shape, np.float64)
    cover = np.zeros(global_shape, np.int16)
    seen: dict[tuple, tuple] = {}   # frozen slice key -> (coords, array)

    for coords_t, arr in shards.items():
        coords = dict(zip(axes, coords_t))
        frags = slices_for_rank(spec, global_shape, sizes, coords)
        key = tuple((s.start, s.stop) for f in frags for s in f)
        if key in seen:
            ref_coords, ref_arr = seen[key]
            denom = np.linalg.norm(ref_arr.astype(np.float64))
            err = np.linalg.norm(arr.astype(np.float64)
                                 - ref_arr.astype(np.float64))
            rel = err / denom if denom > 0 else err
            if rel > replica_rtol:
                report.conflicts.append(
                    {"coords": coords_t, "ref_coords": ref_coords,
                     "rel_err": float(rel)})
                report.ok = False
            continue
        seen[key] = (coords_t, arr)
        # place fragments: multi-fragment shards are concatenated along the
        # cp dim in chunk order, so walk them in the same order.
        off = 0
        cdim = (spec.cp_dim % len(global_shape)
                if (spec.cp_mode == "zigzag" and spec.cp_dim is not None)
                else None)
        for f in frags:
            if cdim is None:
                piece = arr
            else:
                ext = f[cdim].stop - f[cdim].start
                idx = [slice(None)] * arr.ndim
                idx[cdim] = slice(off, off + ext)
                piece = arr[tuple(idx)]
                off += ext
            want = tuple(s.stop - s.start for s in f)
            if piece.shape != want:
                # shard shape contradicts the annotation-derived mapping
                report.layout_mismatches.append(
                    {"coords": coords_t, "expected": want,
                     "actual": piece.shape})
                report.ok = False
                continue
            full[f] += piece.astype(np.float64)
            cover[f] += 1
    report.overlap = int(np.sum(cover > 1))
    report.omission = int(np.sum(cover == 0))
    if report.overlap or report.omission:
        report.ok = False
    return full.astype(np.float32), report


def merge_jax_array(arr, spec: ShardSpec, mesh_axes: dict[str, str],
                    replica_rtol: float = REPLICA_RTOL
                    ) -> tuple[np.ndarray, MergeReport]:
    """Rebuild + verify a sharded ``jax.Array`` against the annotation.

    ``mesh_axes`` maps parallel-axis name ("tp", "dp", ...) to the mesh axis
    name it runs on (e.g. {"dp": "data", "tp": "model"}).
    """
    mesh = arr.sharding.mesh
    sizes = {p: int(mesh.shape[m]) for p, m in mesh_axes.items()}
    report = MergeReport()
    shards = {}
    for sh in arr.addressable_shards:
        didx = {m: int(i) for m, i in zip(
            mesh.axis_names, np.argwhere(
                np.asarray(mesh.devices) == sh.device)[0])}
        coords_t = tuple(didx[mesh_axes[p]] for p in sizes)
        coords = dict(zip(sizes, coords_t))
        expected = slices_for_rank(spec, arr.shape, sizes, coords)
        actual = tuple(
            slice(s.start or 0, s.stop if s.stop is not None else dim)
            for s, dim in zip(sh.index, arr.shape))
        if len(expected) == 1 and expected[0] != actual:
            report.layout_mismatches.append(
                {"coords": coords_t, "expected": expected[0],
                 "actual": actual})
            report.ok = False
        shards[coords_t] = np.asarray(sh.data)
    full, rep2 = merge_shards(shards, spec, sizes, arr.shape, replica_rtol)
    rep2.layout_mismatches.extend(report.layout_mismatches)
    rep2.ok = rep2.ok and report.ok
    return full, rep2


# ---------------------------------------------------------------------------
# Per-rank trace merging (real pipeline schedules, paper Fig 5)
# ---------------------------------------------------------------------------

_LAYER_RE = re.compile(r"^layers\.(\d+)(.*)$")


def canonical_stage_name(name: str, table: list[tuple[int, int]]) -> str:
    """Stage-LOCAL tap/param name -> canonical (global) name via the stage's
    ``(executed, canonical)`` table — the renaming a rank-local trace needs
    before it can align with the single-device reference (paper Fig 5).
    Non-layer names (embedding, final norm, LM head) pass through."""
    m = _LAYER_RE.match(name)
    if not m:
        return name
    local = int(m.group(1))
    if local >= len(table):
        raise KeyError(f"local layer {local} outside a stage table of "
                       f"{len(table)} entries")
    return f"layers.{table[local][1]}{m.group(2)}"


def merge_microbatch_traces(records, tables, n_microbatches: int,
                            place=None):
    """Merge per-(stage, microbatch) rank-local traces into ONE
    reference-shaped trace.

    ``records``: iterable of ``(stage, microbatch, Trace)`` — forward ops
    contribute ``activations`` (plus per-stage ``meta['fwd_order']``),
    backward ops contribute ``act_grads`` and per-microbatch
    ``param_grads`` contributions.  ``tables``: per-stage
    ``(executed, canonical)`` renaming (``parallel.pp1f1b.stage_tables``).
    ``place``: optional device/sharding the merged leaves are gathered to
    (the controller the checker runs on); without it, leaves must already
    be colocated per stage.

    The merge verifies per-rank coverage before any value comparison can
    happen: every (stage, name) must be contributed by every microbatch
    exactly once (overlap/omission otherwise), canonicalized names must
    stay unique across stages within a kind — replicated non-layer params
    (tied embeddings on both pipeline ends) instead SUM, the explicit
    tied-embedding reduction — and activations/activation gradients are
    concatenated along the microbatch (batch) axis in microbatch order
    while parameter gradients accumulate across microbatches.

    Returns ``(merged_trace, MergeReport)``; the report also rides along as
    ``merged.meta['merge_report']`` so downstream checkers surface its
    problems with the step report.
    """
    import jax

    from repro.core import canonical as C
    from repro.core.collector import Section, Trace

    S, M = len(tables), n_microbatches
    report = MergeReport()

    def problem(msg):
        report.rank_problems.append(msg)
        report.ok = False

    per: dict = {C.KIND_ACT: {}, C.KIND_ACT_GRAD: {},
                 C.KIND_PARAM_GRAD: {}}
    fwd_orders: dict = {}
    for stage, mb, tr in records:
        if not (0 <= stage < S and 0 <= mb < M):
            problem(f"record (stage {stage}, mb {mb}) outside the "
                    f"{S}x{M} schedule grid")
            continue
        if len(tr.activations) and stage not in fwd_orders:
            fwd_orders[stage] = list(tr.meta.get("fwd_order")
                                     or tr.activations)
        for kind, acc in per.items():
            sec = tr.section(kind)
            for name in sec:
                by_mb = acc.setdefault((stage, name), {})
                if mb in by_mb:
                    report.overlap += 1
                    problem(f"{kind} {name}: (stage {stage}, mb {mb}) "
                            f"contributed twice")
                    continue
                by_mb[mb] = sec.raw(name)

    def gather(x):
        return jax.device_put(x, place) if place is not None else x

    def full_coverage(kind, stage, name, by_mb) -> bool:
        missing = [m for m in range(M) if m not in by_mb]
        if missing:
            report.omission += len(missing)
            problem(f"{kind} {name}: stage {stage} missing "
                    f"microbatch(es) {missing}")
            return False
        return True

    merged = Trace()
    # activations / activation grads: concat along the microbatch axis
    for kind in (C.KIND_ACT, C.KIND_ACT_GRAD):
        out = merged.section(kind)
        for stage in sorted({s for s, _ in per[kind]}):
            valid = {name: by_mb
                     for (s, name), by_mb in per[kind].items()
                     if s == stage
                     and full_coverage(kind, stage, name, by_mb)}
            if not valid:
                continue
            cat = Section.concat(
                [Section({n: gather(valid[n][m]) for n in valid})
                 for m in range(M)], axis=0)
            for name in cat:
                canon = canonical_stage_name(name, tables[stage])
                if canon in out:
                    problem(f"{kind} {canon}: produced by more than one "
                            f"stage after canonical renaming")
                    continue
                out[canon] = cat.raw(name)
    # parameter grads: accumulate the per-microbatch contributions
    pg = merged.param_grads
    for (stage, name) in sorted(per[C.KIND_PARAM_GRAD],
                                key=lambda sn: sn[0]):
        by_mb = per[C.KIND_PARAM_GRAD][(stage, name)]
        if not full_coverage(C.KIND_PARAM_GRAD, stage, name, by_mb):
            continue
        total = gather(by_mb[0])
        for m in range(1, M):
            total = total + gather(by_mb[m])
        canon = canonical_stage_name(name, tables[stage])
        if canon in pg:
            if name.startswith("layers."):
                problem(f"param_grad {canon}: produced by more than one "
                        f"stage after canonical renaming")
                continue
            pg[canon] = pg.raw(canon) + total   # tied-embedding reduction
        else:
            pg[canon] = total
    order = []
    for stage in sorted(fwd_orders):
        order.extend(canonical_stage_name(n, tables[stage])
                     for n in fwd_orders[stage])
    merged.meta["fwd_order"] = order
    merged.meta["merge_report"] = report
    return merged, report


# ---------------------------------------------------------------------------
# Plan-compiled per-rank merging (the supervised hot path)
# ---------------------------------------------------------------------------
#
# ``merge_microbatch_traces`` re-derives static facts every step: the stage
# tables never change, the canonical renaming never changes, the coverage
# grid of a fixed schedule never changes, and the tied-param groups never
# change — yet the per-step Python loop walks every (stage, microbatch,
# name) cell, verifies it, renames it and issues one eager device op (gather
# / concat / add) per cell.  ``MergePlan`` factors all of that out:
#
# * **build once** — run the exact structural walk of the full merge on a
#   template record set, recording the output layout (per-kind name order,
#   canonical renames, tied-param groups), the coverage verdict (the
#   ``MergeReport`` of any record set with this structure) and the per-stage
#   input indexing;
# * **execute per step** — one cheap record-set signature check, then ONE
#   jitted pack per stage (stacked microbatch concat + fused param-grad
#   accumulation, running on the stage's own device) and one bulk transfer
#   of the packed outputs to the controller; the merged sections are then
#   pure renames of the packed leaves.
#
# Execution is numerically IDENTICAL to the full merge: concatenation is
# exact, and the per-microbatch gradient accumulation keeps the same
# left-to-right chain (XLA does not reassociate float adds).  A record set
# whose structure deviates from the plan (different names, coverage, or
# grid) falls back to the full merge, so structural bugs keep their exact
# diagnostics.


class MergePlan:
    """Build-once merge plan over a fixed per-rank record structure.

    ``build(records, tables, n_microbatches, place=...)`` derives the plan
    from a template record set (typically the first step's); ``execute``
    then merges any same-structured record set in a handful of device
    dispatches.  ``stage_param_grads`` holds, after ``execute``, the
    per-stage accumulated parameter gradients under their stage-LOCAL names
    (already on ``place``) — the 1F1B engine reuses them for the
    executed-index global gradient tree instead of re-accumulating.
    """

    def __init__(self, tables, n_microbatches: int, place=None):
        self.tables = tables
        self.M = n_microbatches
        self.place = place
        self.signature = None
        self._problems: list[str] = []
        self._overlap = self._omission = 0
        self._fwd_order: list[str] = []
        # output layout: [(kind, stage, local name, canonical name)] in the
        # full merge's output order; tied groups: [(canon, [(stage, name)])]
        self._cat_out: list = []
        self._pg_out: list = []
        # per-stage pack inputs: stage -> ([(kind, name, [rec_idx per mb])],
        #                                  [(name, [rec_idx per mb])])
        self._stage_cat: dict = {}
        self._stage_pg: dict = {}
        self._pack = None
        self.stage_param_grads: dict | None = None
        self.executions = 0
        self.fallbacks = 0

    # ---- structural walk (mirrors merge_microbatch_traces exactly) --------
    @staticmethod
    def _sig_of(records) -> tuple:
        return tuple((stage, mb, tuple(tr.activations), tuple(tr.act_grads),
                      tuple(tr.param_grads)) for stage, mb, tr in records)

    @classmethod
    def build(cls, records, tables, n_microbatches: int, place=None
              ) -> "MergePlan":
        from repro.core import canonical as C

        records = list(records)
        plan = cls(tables, n_microbatches, place)
        plan.signature = cls._sig_of(records)
        S, M = len(tables), n_microbatches

        def problem(msg):
            plan._problems.append(msg)

        per: dict = {C.KIND_ACT: {}, C.KIND_ACT_GRAD: {},
                     C.KIND_PARAM_GRAD: {}}
        fwd_orders: dict = {}
        for idx, (stage, mb, tr) in enumerate(records):
            if not (0 <= stage < S and 0 <= mb < M):
                problem(f"record (stage {stage}, mb {mb}) outside the "
                        f"{S}x{M} schedule grid")
                continue
            if len(tr.activations) and stage not in fwd_orders:
                fwd_orders[stage] = list(tr.meta.get("fwd_order")
                                         or tr.activations)
            for kind, acc in per.items():
                for name in tr.section(kind):
                    by_mb = acc.setdefault((stage, name), {})
                    if mb in by_mb:
                        plan._overlap += 1
                        problem(f"{kind} {name}: (stage {stage}, mb {mb}) "
                                f"contributed twice")
                        continue
                    by_mb[mb] = idx

        def full_coverage(kind, stage, name, by_mb) -> bool:
            missing = [m for m in range(M) if m not in by_mb]
            if missing:
                plan._omission += len(missing)
                problem(f"{kind} {name}: stage {stage} missing "
                        f"microbatch(es) {missing}")
                return False
            return True

        for kind in (C.KIND_ACT, C.KIND_ACT_GRAD):
            out_names: set = set()
            for stage in sorted({s for s, _ in per[kind]}):
                valid = {name: by_mb
                         for (s, name), by_mb in per[kind].items()
                         if s == stage
                         and full_coverage(kind, stage, name, by_mb)}
                for name, by_mb in valid.items():
                    canon = canonical_stage_name(name, tables[stage])
                    if canon in out_names:
                        problem(f"{kind} {canon}: produced by more than one "
                                f"stage after canonical renaming")
                        continue
                    out_names.add(canon)
                    plan._cat_out.append((kind, stage, name, canon))
                    plan._stage_cat.setdefault(stage, []).append(
                        (kind, name, [by_mb[m] for m in range(M)]))
        pg_groups: dict = {}
        for (stage, name) in sorted(per[C.KIND_PARAM_GRAD],
                                    key=lambda sn: sn[0]):
            by_mb = per[C.KIND_PARAM_GRAD][(stage, name)]
            if not full_coverage(C.KIND_PARAM_GRAD, stage, name, by_mb):
                continue
            canon = canonical_stage_name(name, tables[stage])
            if canon in pg_groups and name.startswith("layers."):
                problem(f"param_grad {canon}: produced by more than one "
                        f"stage after canonical renaming")
                continue
            if canon not in pg_groups:
                plan._pg_out.append(canon)
            pg_groups.setdefault(canon, []).append((stage, name))
            plan._stage_pg.setdefault(stage, []).append(
                (name, [by_mb[m] for m in range(M)]))
        plan._pg_groups = pg_groups
        order = []
        for stage in sorted(fwd_orders):
            order.extend(canonical_stage_name(n, tables[stage])
                         for n in fwd_orders[stage])
        plan._fwd_order = order
        return plan

    # ---- per-step execution ------------------------------------------------
    @property
    def ok(self) -> bool:
        return not self._problems

    def report(self) -> MergeReport:
        """A fresh MergeReport carrying this structure's (static) verdict."""
        return MergeReport(ok=not self._problems,
                           overlap=self._overlap, omission=self._omission,
                           rank_problems=list(self._problems))

    def matches(self, records) -> bool:
        return self._sig_of(records) == self.signature

    def _packer(self):
        if self._pack is None:
            import functools

            import jax
            import jax.numpy as jnp

            def pack(cats, pgs):
                return ([jnp.concatenate(xs, axis=0) for xs in cats],
                        [xs[0] if len(xs) == 1
                         else functools.reduce(jnp.add, xs) for xs in pgs])

            # per-plan jit wrapper: each plan keeps its own trace cache, so
            # plans over different structures never thrash one another
            self._pack = jax.jit(pack)
        return self._pack

    def execute(self, records):
        """Merge one record set.  Same-structured sets take the compiled
        path; anything else falls back to the full (verifying) merge."""
        import jax

        from repro.core.collector import Trace

        records = list(records)
        if not self.matches(records):
            self.fallbacks += 1
            import warnings
            warnings.warn("MergePlan fallback: the record set no longer "
                          "matches the compiled plan; running the full "
                          "merge", RuntimeWarning)
            self.stage_param_grads = None
            return merge_microbatch_traces(records, self.tables, self.M,
                                           place=self.place)
        self.executions += 1
        pack = self._packer()
        packed_cat: dict = {}
        packed_pg: dict = {}
        for stage in sorted(set(self._stage_cat) | set(self._stage_pg)):
            cats = [[records[i][2].section(kind).raw(name) for i in idxs]
                    for kind, name, idxs in self._stage_cat.get(stage, [])]
            pgs = [[records[i][2].param_grads.raw(name) for i in idxs]
                   for name, idxs in self._stage_pg.get(stage, [])]
            out_c, out_p = pack(cats, pgs)
            if self.place is not None:
                out_c, out_p = jax.device_put((out_c, out_p), self.place)
            for (kind, name, _), leaf in zip(self._stage_cat.get(stage, []),
                                             out_c):
                packed_cat[(kind, stage, name)] = leaf
            for (name, _), leaf in zip(self._stage_pg.get(stage, []), out_p):
                packed_pg[(stage, name)] = leaf

        merged = Trace()
        for kind, stage, name, canon in self._cat_out:
            merged.section(kind)[canon] = packed_cat[(kind, stage, name)]
        pg = merged.param_grads
        for canon in self._pg_out:
            group = self._pg_groups[canon]
            total = packed_pg[group[0]]
            for sn in group[1:]:
                total = total + packed_pg[sn]   # tied-embedding reduction
            pg[canon] = total
        self.stage_param_grads = dict(packed_pg)
        report = self.report()
        merged.meta["fwd_order"] = list(self._fwd_order)
        merged.meta["merge_report"] = report
        return merged, report

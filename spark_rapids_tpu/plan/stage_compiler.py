"""Whole-stage fusion: a pipeline STAGE, not an operator, is the unit of
compiled execution (docs/fusion.md).

Reference: the executor whole-stage codegen pipeline (SURVEY.md §3.3) — the
reference collapses a pipeline-breaker-free operator chain into one generated
function; here the chain lowers to ONE ``_fused_fn`` XLA program per batch.
Eager per-operator execution dispatches one compiled program per operator per
batch (plus a compaction scatter and count per filter); where the host's
dispatch rate, not the device, bounds a query, those per-op dispatches are
most of its wall time.

Three pieces live here:

* :class:`StageChain` — an ordered list of fusable filter/project steps with
  a single traced evaluation (`eval_traced`) used both by
  :class:`TpuWholeStageExec` and by ``TpuHashAggregateExec``'s folded
  ``pre_stage`` (the scan-unpack -> filter -> project -> partial-agg stage:
  the scan's cached unpack program feeds the stage program feeds the
  aggregate kernel — one device program per stage per batch, donation
  threaded through the whole chain).
* :func:`fuse_stages` / :func:`peel_for_aggregate` — the stage compiler
  passes ``Overrides.apply`` runs over the converted exec tree, gated by
  ``spark.rapids.tpu.sql.fusion.wholeStage`` (default on). Every fusion
  decision — membership or decline reason — is recorded per node and
  surfaces in EXPLAIN ANALYZE.
* :func:`tuned_batch_rows` — batch-size autotuning: the scan/coalesce row
  target derived from the device HBM budget and the live watermark
  (service/telemetry), so fused stages run at the largest safe batch.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

from .. import config as cfg
from ..analysis.contracts import exec_contract
from ..columnar import dtypes as dt
from ..columnar.batch import ColumnarBatch
from ..columnar.column import Scalar
from ..ops import expressions as ex
from ..ops import kernels as K
from ..exec.tracing import host_site, operator_scope, trace_span
from . import physical as ph
from .physical import (Partition, TpuExec, _dev_count, _donate_argnums,
                       _donation_consumed, _expr_cache_key, _fused_fn,
                       _schema_sig, _ScalarPredicate, exec_metrics)


# ---------------------------------------------------------------------------
# Stage chain: the fusable operator sequence
# ---------------------------------------------------------------------------

class StageChain:
    """An ordered chain of filter/project steps evaluated in ONE trace.

    Steps apply bottom-up (scan side first). Filters accumulate a live-row
    MASK instead of compacting per step — compaction is a scatter (the
    slowest TPU primitive) and runs at most once, at the stage boundary;
    an aggregate consumer skips it entirely and feeds the mask to its
    kernels. Projects rebuild the virtual batch positionally, so masks
    stay row-aligned across steps.

    steps: [("filter", bound_condition) | ("project", bound_exprs,
    out_schema)] — expressions are already bound to the PREVIOUS step's
    output schema (the original per-op execs bound them).
    """

    def __init__(self, steps: List[tuple], in_schema: dt.Schema,
                 out_schema: dt.Schema):
        self.steps = list(steps)
        self.in_schema = in_schema
        self.out_schema = out_schema
        # query parameters inside the chain (plan-cache parameterization):
        # slot-ordered, each stamped with its trace position — the fused
        # program's appended-argument contract (docs/plan_cache.md)
        self.params = ex.ordered_params(self.exprs())

    # -- static properties ---------------------------------------------------
    def exprs(self) -> List[ex.Expression]:
        out: List[ex.Expression] = []
        for step in self.steps:
            if step[0] == "filter":
                out.append(step[1])
            else:
                out.extend(step[1])
        return out

    def fusable(self) -> bool:
        return all(e.tree_fusable() for e in self.exprs()) and not any(
            e.collect(lambda x: not x.side_effect_free) for e in self.exprs())

    @host_site("program_key")
    def cache_key(self) -> Optional[tuple]:
        """Structural key of the whole chain, or None when any expression
        is unkeyable (the stage then stays on the per-op path — a per-exec
        jit of a multi-op chain would recompile per query)."""
        parts: List[tuple] = []
        traced = ex.traced_literal_ids(self.params)
        for step in self.steps:
            if step[0] == "filter":
                k = _expr_cache_key(step[1], traced)
                if k is None:
                    return None
                parts.append(("filter", k))
            else:
                ks = [_expr_cache_key(e, traced) for e in step[1]]
                if any(k is None for k in ks):
                    return None
                parts.append(("project", tuple(ks),
                              _schema_sig(step[2])))
        return tuple(parts)

    def describe(self) -> str:
        return "->".join("filter" if s[0] == "filter"
                         else f"project[{len(s[1])}]" for s in self.steps)

    # -- traced evaluation ---------------------------------------------------
    def eval_traced(self, b: ColumnarBatch
                    ) -> Tuple[ColumnarBatch, Optional[Any]]:
        """Apply the chain inside a fused trace. Returns (batch, mask):
        ``mask`` is the accumulated live-row mask (None when the chain has
        no filter — every input row is live). Dead rows keep whatever
        garbage the projections computed for them; consumers mask or
        compact before the values matter."""
        import jax
        mask = None
        for step in self.steps:
            # each folded operator under its own scope, as when it ran
            # as an exec of its own
            if step[0] == "filter":
                with operator_scope("TpuFilterExec"), \
                        jax.named_scope("filter"):
                    pred = step[1].eval(b)
                    if isinstance(pred, Scalar):
                        # constant predicate bakes a python bool into the
                        # trace: permanent per-op fallback, like FusedStage
                        raise _ScalarPredicate()
                    m = pred.data & pred.validity
                    mask = m if mask is None else (mask & m)
            else:
                _tag, exprs, out_schema = step
                with operator_scope("TpuProjectExec"), \
                        jax.named_scope("project"):
                    cols = [ex.materialize(e.eval(b), b) for e in exprs]
                nb = ColumnarBatch(out_schema, cols, b.num_rows_raw)
                nb.params = b.params   # later steps' Parameters still read
                b = nb
        if mask is not None:
            with operator_scope("TpuFilterExec"), jax.named_scope("filter"):
                mask = mask & b.row_mask_raw()
        return b, mask

    # -- eager fallback ------------------------------------------------------
    def eval_eager(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Per-op eager evaluation (the pre-fusion semantics): compaction
        per filter step, one dispatch per expression node."""
        b = batch
        for step in self.steps:
            if step[0] == "filter":
                pred = step[1].eval(b)
                if isinstance(pred, Scalar):
                    if pred.value is True:
                        continue
                    b = ColumnarBatch(b.schema, b.columns, 0)
                    continue
                keep = pred.data & pred.validity & b.row_mask()
                cols, count = K.compact_columns(b.columns, keep)
                b = ColumnarBatch(b.schema, cols, count)
            else:
                _tag, exprs, out_schema = step
                cols = [ex.materialize(e.eval(b), b) for e in exprs]
                b = ColumnarBatch(out_schema, cols, b.num_rows_raw)
        return b


def chain_of_filter(condition: ex.Expression,
                    schema: dt.Schema) -> StageChain:
    """Single-filter degenerate chain (the legacy ``pre_filter`` form)."""
    return StageChain([("filter", condition)], schema, schema)


def build_stage_program(chain: StageChain, donate: tuple = ()):
    """The whole-stage jitted program for ``chain`` — module-level (no
    exec instance in the closure) so the compile pool can rebuild the
    IDENTICAL program from a pickled chain in a fresh process (prewarm,
    exec/compile_pool.py) and hit the same ``_fused_fn`` key."""
    import jax
    in_schema = chain.in_schema
    has_filter = any(s[0] == "filter" for s in chain.steps)

    def run(num_rows, *arrays):
        b = ColumnarBatch.from_flat_arrays(in_schema, arrays, num_rows)
        out, mask = chain.eval_traced(b)
        if not has_filter:
            return tuple(out.flat_arrays())
        with operator_scope("TpuWholeStageExec"):
            cols, count = K.compact_columns(out.columns, mask)
        return tuple(a for c in cols for a in c.arrays()) + (count,)
    # lint: naked-jit-ok only ever invoked as a _fused_fn builder (the exec's _build and the compile pool's prewarm replay both route through the funnel)
    return jax.jit(run, donate_argnums=donate)


# ---------------------------------------------------------------------------
# The whole-stage exec
# ---------------------------------------------------------------------------

class TpuWholeStageExec(TpuExec):
    """A fused filter/project chain as ONE exec: per batch, one compiled
    program evaluates every member operator's expressions and compacts
    once at the stage boundary (count left device-resident, like
    TpuFilterExec). Falls back permanently to the per-op eager path on
    any trace failure — identical semantics, more dispatches."""

    CONTRACT = exec_contract(schema="defined", partitioning="preserve")
    METRICS = exec_metrics()

    def __init__(self, child: TpuExec, chain: StageChain,
                 members: List[str], stage_id: int = 0):
        super().__init__(child)
        self.chain = chain
        self.members = members          # bottom-up member exec names
        self.stage_id = stage_id
        self.broken = False
        self._fns: Dict[bool, Any] = {}   # donate bit -> program
        self._has_filter = any(s[0] == "filter" for s in chain.steps)
        self._ckey = chain.cache_key()

    @property
    def schema(self) -> dt.Schema:
        return self.chain.out_schema

    def execute(self) -> List[Partition]:
        return [self._map(p) for p in self.children[0].execute()]

    def _build(self, donate: tuple = ()):
        return build_stage_program(self.chain, donate)

    def _stage_args(self, batch: ColumnarBatch) -> tuple:
        """The fused program's real argument tuple for ``batch`` (the
        exact avals ``_fused`` calls with)."""
        return (_dev_count(batch), *batch.flat_arrays(),
                *ex.param_arg_values(self.chain.params))

    @staticmethod
    def _warm_args(args: tuple) -> tuple:
        """Zero-filled stand-ins for a pool warm call: ``zeros_like``
        preserves shape/dtype/weak-type, so the background compile's jit
        signature exactly matches the real call — without aliasing this
        batch's (possibly soon-donated) buffers on another thread."""
        import jax
        import jax.numpy as jnp
        return tuple(jnp.zeros_like(a) if isinstance(a, jax.Array) else a
                     for a in args)

    def _fused(self, batch: ColumnarBatch) -> Optional[ColumnarBatch]:
        from ..analysis import recompile as _recompile
        from ..exec import compile_pool as _pool
        try:
            donate = _donate_argnums(batch, 1)
            fn = self._fns.get(bool(donate))
            if fn is None:
                # no capacity in the key: like FusedStage, one program per
                # expression structure — jax retraces per batch shape under
                # the same cached callable
                key = ("stage", _schema_sig(self.chain.in_schema),
                       self._ckey, ("donate", bool(donate)))
                self._kernel = _recompile.kernel_of(key)
                st = _pool.status(key)
                if st is None and not ph.fused_cached(key) and \
                        _pool.routable(key):
                    # latency-sensitive cold build: hand it to the pool
                    # and serve this batch eagerly (docs/compile.md §5)
                    args = self._stage_args(batch)
                    _pool.note_stage_signature(key, self._kernel,
                                               self.chain, donate, args)
                    st = _pool.consult(key, lambda: self._build(donate),
                                       self._warm_args(args),
                                       kernel=self._kernel)
                if st == "pending":
                    return None    # eager until the background build lands
                if st == "failed":
                    err = _pool.failure(key)
                    if err is not None:
                        # replicate the synchronous failure semantics:
                        # the except arms below decide broken vs raise
                        raise err
                if not ph.fused_cached(key):
                    # record the rebuild recipe for prewarm BEFORE the
                    # build (sync path; the async path recorded above)
                    _pool.note_stage_signature(key, self._kernel,
                                               self.chain, donate,
                                               self._stage_args(batch))
                fn = _fused_fn(key, lambda: self._build(donate))
                self._fns[bool(donate)] = fn
            with trace_span("fused_stage"):
                outs = fn(_dev_count(batch), *batch.flat_arrays(),
                          *ex.param_arg_values(self.chain.params))
            ph._note_donated(batch, donate)
        except _ScalarPredicate:
            self.broken = True
            return None
        except Exception as e:
            if _donation_consumed(batch):
                raise          # executed-and-donated: no eager re-read
            import logging
            logging.getLogger("spark_rapids_tpu.fusion").warning(
                "whole-stage program fell back to per-op eager for stage "
                "#%d (%s): %s", self.stage_id, "+".join(self.members), e)
            self.broken = True
            return None
        if not self._has_filter:
            return ColumnarBatch.from_flat_arrays(
                self.chain.out_schema, list(outs), batch.num_rows_raw)
        # filtered: compacted columns + device count (no readback — the
        # count rides downstream like TpuFilterExec's)
        return ColumnarBatch.from_flat_arrays(
            self.chain.out_schema, list(outs[:-1]), outs[-1])

    def _map(self, part: Partition) -> Partition:
        for batch in part:
            if isinstance(batch.num_rows_raw, int) and \
                    batch.num_rows_raw == 0:
                continue
            with trace_span(f"op_{type(self).__name__}", self.metrics,
                            "opTime"):
                out = None
                if not self.broken:
                    out = self._fused(batch)
                if out is None:
                    out = self.chain.eval_eager(batch)
            self.metrics.inc("numOutputRows", out.num_rows_raw)
            self.metrics.inc("numOutputBatches")
            yield out

    def _node_string(self) -> str:
        return (f"TpuWholeStageExec[#{self.stage_id} "
                f"{'+'.join(self.members)}]")


# ---------------------------------------------------------------------------
# The planner passes
# ---------------------------------------------------------------------------

def fusion_enabled(conf: cfg.TpuConf) -> bool:
    # the legacy wholeStageFusion.enabled is the MASTER fusion switch
    # (it gates the per-op FusedStage programs at runtime): turning it
    # off must disable stage-level fusion too, or an operator A/B-ing
    # "fusion off" would still get fused chains
    return bool(conf.get(cfg.FUSION_WHOLE_STAGE)) and \
        bool(conf.get(cfg.WHOLESTAGE_FUSION))


def _node_decline_reason(node: TpuExec) -> Optional[str]:
    """Why this filter/project exec cannot join a fused stage (None when
    it can)."""
    if isinstance(node, ph.TpuProjectExec):
        exprs = node.exprs
    elif isinstance(node, ph.TpuFilterExec):
        exprs = [node.condition]
    else:
        return f"not a stage operator ({type(node).__name__})"
    for e in exprs:
        bad = e.collect(lambda x: not x.side_effect_free)
        if bad:
            return f"stateful expression ({type(bad[0]).__name__})"
        if not e.tree_fusable():
            nf = e.collect(lambda x: not x.fusable)
            which = type(nf[0]).__name__ if nf else type(e).__name__
            return f"expression not fusable ({which})"
        if _expr_cache_key(e) is None:
            return "unkeyable expression (per-exec jit only)"
    return None


def _step_of(node: TpuExec) -> tuple:
    if isinstance(node, ph.TpuFilterExec):
        return ("filter", node.condition)
    return ("project", node.exprs, node.schema)


class FusionDecisions:
    """Per-query record of what the stage compiler did: stage membership
    for fused nodes, decline reasons for the rest. Rendered into EXPLAIN
    ANALYZE next to the contract diagnostics."""

    def __init__(self):
        self.notes: List[str] = []     # plan-level summary lines
        self._n = 0

    def next_stage_id(self) -> int:
        self._n += 1
        return self._n

    def note(self, line: str) -> None:
        self.notes.append(line)


def peel_for_aggregate(child: TpuExec, decisions: FusionDecisions
                       ) -> Tuple[TpuExec, Optional[StageChain], List[str]]:
    """Walk down a fusable filter/project chain directly below an
    aggregate and fold it into the aggregate's own fused programs
    (``pre_stage``): the whole scan -> filter -> project -> partial-agg
    stage becomes the agg's update program — no separate per-op dispatch,
    compaction, or count sync per batch. Returns (new child, chain or
    None, member names bottom-up)."""
    steps_top_down: List[tuple] = []
    members_top_down: List[str] = []
    node = child
    while isinstance(node, (ph.TpuFilterExec, ph.TpuProjectExec)):
        reason = _node_decline_reason(node)
        if reason is not None:
            node._fusion_decline = reason
            break
        steps_top_down.append(_step_of(node))
        members_top_down.append(type(node).__name__)
        node = node.children[0]
    if not steps_top_down:
        return child, None, []
    steps = list(reversed(steps_top_down))
    members = list(reversed(members_top_down))
    chain = StageChain(steps, node.schema, child.schema)
    if chain.cache_key() is None:
        return child, None, []
    return node, chain, members


def fuse_stages(root: TpuExec, conf: cfg.TpuConf,
                decisions: FusionDecisions) -> TpuExec:
    """Collapse every remaining maximal filter/project chain (length >= 2)
    into a :class:`TpuWholeStageExec`. Single operators keep the existing
    per-op ``FusedStage`` path — already one program per batch; wrapping
    them would only rename the node."""

    def rec(node: TpuExec) -> TpuExec:
        if isinstance(node, (ph.TpuFilterExec, ph.TpuProjectExec)):
            run: List[TpuExec] = []
            cur = node
            while isinstance(cur, (ph.TpuFilterExec, ph.TpuProjectExec)):
                reason = _node_decline_reason(cur)
                if reason is not None:
                    cur._fusion_decline = reason
                    break
                run.append(cur)
                cur = cur.children[0]
            if len(run) >= 2:
                steps = [_step_of(n) for n in reversed(run)]
                members = [type(n).__name__ for n in reversed(run)]
                chain = StageChain(steps, run[-1].children[0].schema,
                                   run[0].schema)
                if chain.cache_key() is not None:
                    ws = TpuWholeStageExec(rec(run[-1].children[0]), chain,
                                           members,
                                           decisions.next_stage_id())
                    decisions.note(
                        f"stage #{ws.stage_id}: {'+'.join(members)} -> "
                        f"one fused program per batch")
                    return ws
                run[0]._fusion_decline = \
                    "unkeyable expression in chain (per-exec jit only)"
            elif run:
                run[0]._fusion_single = True
        for i, c in enumerate(node.children):
            node.children[i] = rec(c)
        return node

    return rec(root)


def fusion_annotations(root: TpuExec) -> Dict[str, List[str]]:
    """Per-node EXPLAIN ANALYZE annotations keyed by the same
    root->node class-name path the contract validator uses: fused-stage
    membership for stage nodes and folded aggregates, decline reasons for
    operators left on the per-op path."""
    out: Dict[str, List[str]] = {}

    def walk(node, path: str, idx: Optional[int] = None) -> None:
        name = type(node).__name__
        here = f"{path}/{idx}.{name}" if path else name
        lines: List[str] = []
        if isinstance(node, TpuWholeStageExec):
            lines.append(
                f"* fused stage #{node.stage_id}: "
                f"{'+'.join(node.members)} compiled into one program"
                + (" (fell back to per-op eager)" if node.broken else ""))
        stage = getattr(node, "_fusion_stage", None)
        if stage is not None:
            members = getattr(node, "_fusion_members", [])
            lines.append(
                f"* fused stage #{stage}: {'+'.join(members)} folded into "
                f"this aggregate's update program")
        reason = getattr(node, "_fusion_decline", None)
        if reason is not None:
            lines.append(f"* fusion declined: {reason}")
        if getattr(node, "_fusion_single", False):
            lines.append("* single-op stage (per-op fused program)")
        if lines:
            out[here] = lines
        for i, c in enumerate(getattr(node, "children", ())):
            walk(c, here, i)

    walk(root, "")
    return out


# ---------------------------------------------------------------------------
# Batch-size autotuning (ISSUE 11 prong c)
# ---------------------------------------------------------------------------

# per-process memo: (row_bytes bucket, ceiling) -> rows. The first
# computation reads the live HBM watermark; later queries reuse the pick so
# repeated runs see identical batch capacities (the recompile gate depends
# on shape stability, and the pow2 quantization already absorbs small
# watermark drift).
_TUNE_CACHE: Dict[tuple, int] = {}
_tune_lock = threading.Lock()

# a fused stage holds ~input + output + temporaries per resident batch;
# streaming pipelines (agg window, task pool) keep several batches in
# flight. 12 resident batches x 2x working set has held the measured
# corpus under budget while leaving headroom for the spill store.
_RESIDENT_BATCHES = 12
_BUDGET_FRACTION = 0.5


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def _pow2_ceil(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _device_budget_bytes() -> int:
    from ..exec.device import DeviceManager
    dm = DeviceManager.peek()
    if dm is not None:
        return int(dm.memory_budget_bytes)
    return 2 << 30          # DeviceManager's own CPU-fallback budget


def _row_bytes(schema: dt.Schema) -> int:
    total = 0
    for f in schema:
        total += (f.dtype.byte_width or 32) + 1
    return max(total, 1)


def tuned_batch_rows(conf: cfg.TpuConf, schema: dt.Schema) -> int:
    """Scan/coalesce target rows per batch: the largest SAFE batch for a
    fused stage over ``schema`` (docs/fusion.md §4).

    With ``spark.rapids.tpu.sql.batch.autotune`` (default on) the target
    is ``min(batchSizeBytes, available HBM share) / row_bytes`` quantized
    to a power of two — available = device budget minus the live device
    watermark (service/telemetry), shared across ~12 resident batches at
    half occupancy. An explicit ``reader.batchSizeRows`` setting stays a
    hard user cap. Autotune off reproduces the legacy bytes-derived
    target capped at reader.batchSizeRows."""
    row_bytes = _row_bytes(schema)
    reader_cap = int(conf.get(cfg.MAX_READER_BATCH_SIZE_ROWS))
    # caps apply AFTER the floor: an explicit small reader.batchSizeRows
    # must win over the 16k floor (tests pin tiny batches to force
    # multi-batch streams)
    legacy = min(max(1 << 14, int(conf.batch_size_bytes) // row_bytes),
                 reader_cap)
    if not bool(conf.get(cfg.BATCH_AUTOTUNE)):
        return legacy
    ceiling = int(conf.get(cfg.BATCH_AUTOTUNE_MAX_ROWS))
    if cfg.MAX_READER_BATCH_SIZE_ROWS.key in conf._settings:
        # the user pinned a rows cap: autotune may shrink below it under
        # memory pressure but never exceed it
        ceiling = min(ceiling, reader_cap)
    # the division uses the pow2-CEIL of the row width so the pick is a
    # pure (deterministic) function of the memo key — stable capacities
    # are what the recompile gate enforces. batchSizeBytes participates
    # in the computation, so it must participate in the key (a session
    # that lowers it must not hit another session's larger pick)
    rb = _pow2_ceil(row_bytes)
    memo_key = (rb, ceiling, int(conf.batch_size_bytes))
    with _tune_lock:
        hit = _TUNE_CACHE.get(memo_key)
    if hit is not None:
        return hit
    budget = _device_budget_bytes()
    try:
        from ..service.telemetry import watermark
        in_use = int(watermark("device").current)
    except Exception:
        in_use = 0
    avail = max(budget - in_use, budget // 4)
    share = int(avail * _BUDGET_FRACTION) // _RESIDENT_BATCHES
    per_batch_bytes = min(int(conf.batch_size_bytes), max(share, 1))
    rows = min(max(1 << 14, per_batch_bytes // rb), ceiling)
    rows = _pow2_floor(rows)
    with _tune_lock:
        _TUNE_CACHE.setdefault(memo_key, rows)
        rows = _TUNE_CACHE[memo_key]
    return rows


def reset_tuning_cache() -> None:
    with _tune_lock:
        _TUNE_CACHE.clear()

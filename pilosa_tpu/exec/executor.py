"""Query executor: per-call dispatch + per-shard map + reduce.

Reference: /root/reference/executor.go — executeCall dispatch (:274-339),
per-shard mapReduce (:2460-2613), per-call implementations (:360-2418).

TPU-first structure: every bitmap call lowers, per shard, to dense device
words; cross-child algebra happens on device; cross-shard reduction happens
with exact host ints (counts) or segment maps (rows). The single-node
executor walks shards in a Python loop — the mesh path (parallel/) stacks
shards into one [n_shards, W] sharded array and jits the whole map+reduce
with collectives; both share the per-shard lowering here.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from pilosa_tpu.core import resultcache as rcache
from pilosa_tpu.core import timeq
from pilosa_tpu.core.field import (
    FIELD_TYPE_BOOL,
    FIELD_TYPE_INT,
    FIELD_TYPE_TIME,
    Field,
)
from pilosa_tpu.core.fragment import BSI_EXISTS_BIT, BSI_OFFSET_BIT, BSI_SIGN_BIT
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.index import Index
from pilosa_tpu.core.row import Row
from pilosa_tpu.core.rowsummary import Cells, cells_of, head_per_segment
from pilosa_tpu.core.view import VIEW_STANDARD
from pilosa_tpu.exec import translation
from pilosa_tpu.exec.plan import (
    BudgetExceeded,
    MultiCountPlan,
    PLeaf,
    PNary,
    PNode,
    PRangeBetween,
    PRangeCmp,
    PRangeEQ,
    PShift,
    PZero,
    SparseView,
    StackedPlan,
    Unsupported,
    lower_span,
)
from pilosa_tpu.ops import bitmap as ob
from pilosa_tpu.pql import Call, Query, parse
from pilosa_tpu.pql.ast import BETWEEN, EQ, GT, GTE, LT, LTE, NEQ, Condition
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.stats import PROCESS

DEFAULT_MIN_THRESHOLD = 1  # reference: defaultMinThreshold, executor.go


class ExecError(Exception):
    pass


class NotFoundError(ExecError):
    pass


@dataclass
class ExecOptions:
    remote: bool = False
    exclude_row_attrs: bool = False
    exclude_columns: bool = False
    column_attrs: bool = False
    shards: Optional[List[int]] = None
    max_writes: int = 5000  # reference: MaxWritesPerRequest


@dataclass
class ColumnAttrSet:
    """Column attributes attached to a query response when columnAttrs=true
    (reference: ColumnAttrSet; executor.go:208 readColumnAttrSets)."""

    id: int = 0
    key: Optional[str] = None
    attrs: Optional[Dict[str, Any]] = None

    def to_json(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"attrs": self.attrs or {}}
        if self.key is not None:
            out["key"] = self.key
        else:
            out["id"] = self.id
        return out


@dataclass
class QueryResponse:
    """Execute() response: per-call results plus optional column attr sets
    (reference: QueryResponse, executor.go:113-205). `profile` carries the
    assembled cross-node trace tree when the query ran with the
    `profile=true` option (server/api.py attaches it)."""

    results: List[Any]
    column_attr_sets: Optional[List[ColumnAttrSet]] = None
    profile: Optional[dict] = None


@dataclass
class Pair:
    """TopN result entry (reference: Pair, cache.go:317)."""

    id: int
    count: int
    key: Optional[str] = None

    def to_json(self):
        d = {"id": self.id, "count": self.count}
        if self.key is not None:
            d["key"] = self.key
        return d


@dataclass
class ValCount:
    """Sum/Min/Max result (reference: ValCount, executor.go)."""

    value: int
    count: int

    def to_json(self):
        return {"value": self.value, "count": self.count}


@dataclass
class FieldRow:
    field: str
    row_id: int
    row_key: Optional[str] = None

    def to_json(self):
        if self.row_key:
            return {"field": self.field, "rowKey": self.row_key}
        return {"field": self.field, "rowID": self.row_id}


@dataclass
class GroupCount:
    """One group of a GroupBy. `sum` is set only when the GroupBy carried
    `aggregate=Sum(field=)`: the exact sum of that field over the group's
    columns that hold a value; without the argument the JSON has no such
    key."""

    group: List[FieldRow]
    count: int
    sum: Optional[int] = None

    def to_json(self):
        out = {"group": [g.to_json() for g in self.group], "count": self.count}
        if self.sum is not None:
            out["sum"] = self.sum
        return out

    def compare_key(self):
        return tuple(g.row_id for g in self.group)


@dataclass
class _TopNSpec:
    """Parsed + validated TopN arguments, shared by the batched and
    per-shard paths (reference: fragment.go:1560 topOptions)."""

    f: Field
    n: int
    ids: Optional[list]
    threshold: int
    attr_name: Optional[str]
    filters: Optional[set]
    tanimoto: int
    src_call: Optional[Call]


# TopN dispatch accounting: tests assert the batched path issues O(1)
# device tallies per pass, never one per shard.
TOPN_STATS = {"batched": 0, "fallback": 0, "tally_evals": 0, "one_pass": 0}


class _TallyBundle:
    """Prepared filtered-TopN tally inputs (dense/sparse candidate split +
    device gather entries). Lives in the process-wide DEVICE_CACHE —
    thread-safe, HBM-budgeted, owner-invalidated — keyed by (view stack
    token, candidates, shards, fragment versions); `nbytes` makes the
    budget see the pinned device arrays."""

    __slots__ = ("dense_rows", "sparse_rows", "dev")

    def __init__(self, dense_rows, sparse_rows, dev):
        self.dense_rows = dense_rows
        self.sparse_rows = sparse_rows
        self.dev = dev

    @property
    def nbytes(self) -> int:
        if self.dev is None:
            return 64
        return sum(int(a.nbytes) for a in self.dev[:4])

# Per-shard fallback accounting: host reads are fused in chunks, so a
# 100-shard fallback query does ~2 device->host syncs, not 100.
FALLBACK_STATS = {"count_reads": 0}
_FALLBACK_READ_CHUNK = 64


_COND_OP_NAME = {EQ: "eq", NEQ: "neq", LT: "lt", LTE: "lte", GT: "gt", GTE: "gte"}

# The differential tests patch this to False to run the per-shard loop as
# their reference; it goes with that loop (ROADMAP C3).
_STACKED_ENABLED = True


class _StackedLowering:
    """Lower a PQL bitmap call tree to a compiled plan over stacked
    [S, W] operands (exec/plan.py).

    Mirrors the per-shard lowering's semantic checks exactly — semantic
    errors raise ExecError (propagated to the caller identically on either
    path); shapes with no stacked form raise plan.Unsupported, which makes
    the executor fall back to the per-shard loop. Absent rows/views lower
    to PZero (all-zero stacks behave identically to the serial path's None:
    zero bits in, zero bits out)."""

    def __init__(
        self,
        ex: "Executor",
        idx: Index,
        shards: List[int],
        collect: bool = False,
        no_sparse_guard: bool = False,
    ):
        from pilosa_tpu.hbm import residency as hbm_res

        self.ex = ex
        self.idx = idx
        self.shards = list(shards)
        self.operands: List[Any] = []
        self.scalars: List[int] = []
        # extent pins taken while staging this lowering's operand stacks
        # (hbm/residency.py): ownership transfers to the lowered plan,
        # which releases them after its compiled dispatch; every failure
        # path below must release instead (no pin may outlive its query)
        self.extents = hbm_res.ExtentTable()
        self._call_memo: Dict[int, PNode] = {}
        self._leaf_memo: Dict[Tuple, Any] = {}
        # collect mode: walk the tree recording touched views (semantic
        # checks still raise) without building any stacks — the pre-pass
        # for compacted (sparse) lowering. no_sparse_guard: the shard list
        # was already compacted to present shards; only the budget applies.
        self.collect = collect
        self.no_sparse_guard = no_sparse_guard
        self.views: Dict[int, Any] = {}  # id(view) -> view, insertion order

    # -- operand registration ---------------------------------------------

    def _stack_guard(self, view, mult: int = 1) -> None:
        """Refuse stacked lowering when densifying would blow memory: a view
        materialized in few of many shards raises SparseView (recovered by
        compacted re-lowering), a stack bigger than a quarter of the device
        budget raises BudgetExceeded (recovered by shard-axis chunking —
        callers that can chunk must let it propagate, _chunk_by_budget)."""
        from pilosa_tpu.core.devcache import DEVICE_CACHE
        from pilosa_tpu.shardwidth import WORDS_PER_ROW

        n = len(self.shards)
        if n >= 64 and not self.no_sparse_guard:
            present = sum(
                1 for s in self.shards if view.fragment_if_exists(s) is not None
            )
            if present and present * 8 < n:
                raise SparseView("sparse view: stacked form would densify")
        if n * WORDS_PER_ROW * 4 * max(mult, 1) > DEVICE_CACHE.budget_bytes // 4:
            raise BudgetExceeded("stack exceeds device budget")

    def _view_leaf(self, view, row_id: int) -> PNode:
        key = ("row", id(view), row_id)
        node = self._leaf_memo.get(key)
        if node is None:
            self.views.setdefault(id(view), view)
            if self.collect:
                # pretend data exists everywhere so the whole tree is
                # walked and every reachable view is recorded
                node = PLeaf(0)
            else:
                self._stack_guard(view)
                arr = view.row_stack(row_id, self.shards, extents=self.extents)
                if arr is None:
                    node = PZero()
                else:
                    self.operands.append(arr)
                    node = PLeaf(len(self.operands) - 1)
            self._leaf_memo[key] = node
        return node

    def _plane_slot(self, view, bit_depth: int) -> Optional[int]:
        key = ("planes", id(view), bit_depth)
        if key not in self._leaf_memo:
            self.views.setdefault(id(view), view)
            if self.collect:
                self._leaf_memo[key] = 0
                return 0
            self._stack_guard(view, mult=bit_depth)
            arr = view.plane_stack(
                range(BSI_OFFSET_BIT, BSI_OFFSET_BIT + bit_depth),
                self.shards,
                extents=self.extents,
            )
            if arr is None:
                self._leaf_memo[key] = None
            else:
                self.operands.append(arr)
                self._leaf_memo[key] = len(self.operands) - 1
        return self._leaf_memo[key]

    def _scalar(self, v: int) -> int:
        self.scalars.append(int(v))
        return len(self.scalars) - 1

    # -- call lowering ------------------------------------------------------

    def lower(self, c: Call) -> PNode:
        node = self._call_memo.get(id(c))
        if node is None:
            node = self._lower(c)
            self._call_memo[id(c)] = node
        return node

    def _lower(self, c: Call) -> PNode:
        name = c.name
        if name in ("Row", "Range"):
            return self._lower_row(c)
        if name == "Intersect":
            if not c.children:
                raise ExecError("empty Intersect query is currently not supported")
            ch = tuple(self.lower(x) for x in c.children)
            if any(isinstance(x, PZero) for x in ch):
                return PZero()
            return ch[0] if len(ch) == 1 else PNary("and", ch)
        if name in ("Union", "Xor"):
            ch = tuple(
                x
                for x in (self.lower(x) for x in c.children)
                if not isinstance(x, PZero)
            )
            if not ch:
                return PZero()
            if len(ch) == 1:
                return ch[0]
            return PNary("or" if name == "Union" else "xor", ch)
        if name == "Difference":
            if not c.children:
                return PZero()
            ch = tuple(self.lower(x) for x in c.children)
            if isinstance(ch[0], PZero):
                return PZero()
            rest = tuple(x for x in ch[1:] if not isinstance(x, PZero))
            if not rest:
                return ch[0]
            return PNary("andnot", (ch[0],) + rest)
        if name == "Not":
            if not self.idx.track_existence:
                raise ExecError("Not() query requires existence tracking to be enabled")
            if len(c.children) != 1:
                raise ExecError("Not() requires a single bitmap input")
            exists = self._existence_leaf()
            if isinstance(exists, PZero):
                return PZero()
            child = self.lower(c.children[0])
            if isinstance(child, PZero):
                return exists
            return PNary("andnot", (exists, child))
        if name == "All":
            return self._existence_leaf()
        if name == "Shift":
            if len(c.children) != 1:
                raise ExecError("Shift() requires a single bitmap input")
            n = c.int_arg("n")
            n = 1 if n is None else n
            child = self.lower(c.children[0])
            if isinstance(child, PZero):
                return PZero()
            return PShift(child, n, self._prev_idx())
        raise Unsupported(name)

    def _existence_leaf(self) -> PNode:
        ef = self.idx.existence_field()
        if ef is None:
            raise ExecError("existence field not available")
        v = ef.view(VIEW_STANDARD)
        if v is None:
            return PZero()
        return self._view_leaf(v, 0)

    def _prev_idx(self) -> Tuple[int, ...]:
        """Stack index of shard_id-1 per stack position (-1 = absent),
        padded out to the mesh-padded stack length."""
        from pilosa_tpu.parallel.mesh import padded_shards

        pos = {s: i for i, s in enumerate(self.shards)}
        out = [pos.get(s - 1, -1) for s in self.shards]
        out += [-1] * (padded_shards(len(self.shards)) - len(self.shards))
        return tuple(out)

    def _lower_row(self, c: Call) -> PNode:
        ex, idx = self.ex, self.idx
        if c.has_conditions():
            return self._lower_row_bsi(c)
        field_name = ex._field_arg_name(c)
        f = ex._field_of(idx, field_name)
        row_id = c.args.get(field_name)
        if isinstance(row_id, bool):
            if f.options.type != FIELD_TYPE_BOOL:
                raise ExecError("Row() bool value requires a bool field")
            row_id = 1 if row_id else 0
        if not isinstance(row_id, int):
            if isinstance(row_id, str):
                raise ExecError(
                    f"string row key {row_id!r} requires field keys (translation)"
                )
            raise ExecError("Row() must specify a row")
        if f.options.type == FIELD_TYPE_BOOL and row_id not in (0, 1):
            raise ExecError("Row() bool field expects row 0 or 1")

        from_arg = c.args.get("from")
        to_arg = c.args.get("to")
        if from_arg is None and to_arg is None:
            v = f.view(VIEW_STANDARD)
            if v is None:
                return PZero()
            return self._view_leaf(v, row_id)

        if f.options.type != FIELD_TYPE_TIME:
            raise ExecError(f"field {field_name} is not a time field")
        quantum = f.options.time_quantum
        from_t = timeq.parse_time(from_arg) if from_arg is not None else None
        to_t = timeq.parse_time(to_arg) if to_arg is not None else None
        if from_t is None or to_t is None:
            lo, hi = ex._field_time_bounds(f)
            if lo is None:
                return PZero()
            from_t = from_t or lo
            to_t = to_t or hi
        leaves = []
        for vname in timeq.views_by_time_range(VIEW_STANDARD, from_t, to_t, quantum):
            v = f.view(vname)
            if v is None:
                continue
            leaf = self._view_leaf(v, row_id)
            if not isinstance(leaf, PZero):
                leaves.append(leaf)
        if not leaves:
            return PZero()
        return leaves[0] if len(leaves) == 1 else PNary("or", tuple(leaves))

    def _lower_row_bsi(self, c: Call) -> PNode:
        """Stacked BSI condition row: same sign/saturation decomposition as
        Fragment.range_op/range_between (fragment.py), emitted as plan
        nodes over [D, S, W] plane stacks."""
        ex, idx = self.ex, self.idx
        conds = c.condition_args()
        if len(c.args) != 1 or len(conds) != 1:
            raise ExecError("Row(): exactly one condition required")
        field_name, cond = next(iter(conds.items()))
        f = ex._field_of(idx, field_name)
        if f.options.type != FIELD_TYPE_INT:
            raise ExecError(f"field {field_name} is not an int field")
        o = f.options
        bsiv = f.view(f.bsi_view_name())
        if bsiv is None:
            return PZero()
        exists = self._view_leaf(bsiv, BSI_EXISTS_BIT)
        if isinstance(exists, PZero):
            return PZero()
        sign = self._view_leaf(bsiv, BSI_SIGN_BIT)
        planes = self._plane_slot(bsiv, o.bit_depth)
        if planes is None:
            return PZero()

        if cond.op == NEQ and cond.value is None:  # != null
            return exists
        if cond.op == BETWEEN:
            lo, hi = cond.int_pair()
            blo, bhi, out_of_range = f.base_value_between(lo, hi)
            if out_of_range:
                return PZero()
            if lo <= o.min and hi >= o.max:
                return exists
            return self._between(exists, sign, planes, blo, bhi)

        if not isinstance(cond.value, int) or isinstance(cond.value, bool):
            raise ExecError("Row(): conditions only support integer values")
        value = cond.value
        op = _COND_OP_NAME[cond.op]
        base_value, out_of_range = f.base_value(op, value)
        if out_of_range and cond.op != NEQ:
            return PZero()
        if (
            (cond.op == LT and value > o.max)
            or (cond.op == LTE and value >= o.max)
            or (cond.op == GT and value < o.min)
            or (cond.op == GTE and value <= o.min)
        ):
            return exists
        if out_of_range and cond.op == NEQ:
            return exists
        return self._range_op(exists, sign, planes, op, base_value)

    @staticmethod
    def _pos_neg(exists: PNode, sign: PNode) -> Tuple[PNode, PNode]:
        return PNary("andnot", (exists, sign)), PNary("and", (exists, sign))

    def _range_op(self, exists, sign, planes: int, op: str, predicate: int) -> PNode:
        upred = self._scalar(abs(predicate))
        positives, negatives = self._pos_neg(exists, sign)
        if op in ("eq", "neq"):
            base = negatives if predicate < 0 else positives
            eq = PRangeEQ(base, planes, upred)
            if op == "eq":
                return eq
            return PNary("andnot", (exists, eq))
        if op in ("lt", "lte"):
            allow_eq = op == "lte"
            if predicate > 0 or (predicate == 0 and allow_eq):
                pos = PRangeCmp("lt", positives, planes, upred, allow_eq)
                return PNary("or", (negatives, pos))
            if predicate == 0:  # strict < 0
                return negatives
            return PRangeCmp("gt", negatives, planes, upred, allow_eq)
        if op in ("gt", "gte"):
            allow_eq = op == "gte"
            if predicate > 0 or (predicate == 0 and allow_eq):
                return PRangeCmp("gt", positives, planes, upred, allow_eq)
            if predicate == 0:  # strict > 0
                return PRangeCmp("gt", positives, planes, upred, False)
            neg = PRangeCmp("lt", negatives, planes, upred, allow_eq)
            return PNary("or", (positives, neg))
        raise ExecError(f"invalid range op {op!r}")

    def _between(self, exists, sign, planes: int, pmin: int, pmax: int) -> PNode:
        positives, negatives = self._pos_neg(exists, sign)
        if pmin >= 0:
            return PRangeBetween(
                positives, planes, self._scalar(abs(pmin)), self._scalar(abs(pmax))
            )
        if pmax < 0:
            return PRangeBetween(
                negatives, planes, self._scalar(abs(pmax)), self._scalar(abs(pmin))
            )
        pos = PRangeCmp("lt", positives, planes, self._scalar(abs(pmax)), True)
        neg = PRangeCmp("lt", negatives, planes, self._scalar(abs(pmin)), True)
        return PNary("or", (pos, neg))


# ---------------------------------------------------------------------------
# Versioned result cache (core/resultcache.py): eligibility surface.
# A call is cacheable when its referenced (field, view) set is STATICALLY
# enumerable — anything data-dependent (time-quantum view discovery) or
# version-blind (row attrs) makes it ineligible and it executes normally.
# ---------------------------------------------------------------------------

_CACHE_KINDS = {"Count": "count", "TopN": "topn", "GroupBy": "groupby"}


def _cache_kind(c: Call) -> Optional[str]:
    """The result cache's kind for a call; None for a call it never keeps,
    or while it is off."""
    if rcache.RESULT_CACHE.budget_bytes <= 0:
        return None
    return _CACHE_KINDS.get(c.name)


_CACHE_BITMAP_OK = frozenset(
    {"Row", "Union", "Intersect", "Difference", "Xor", "Not", "All",
     "Shift", "Range"}
)
# args whose presence means time-view discovery (data-dependent views)
_CACHE_TIME_ARGS = ("from", "to", "_start", "_end")
# TopN attrName/attrValues/tanimotoThreshold read row attrs / source
# counts outside the version vector — ineligible
_CACHE_TOPN_ARGS = frozenset({"_field", "n", "ids", "threshold"})
_CACHE_GROUPBY_ARGS = frozenset({"filter", "limit", "offset", "previous"})
# every argument _execute_group_by reads; anything else is refused by name.
# A GroupBy with aggregate= is never kept by the result cache (nor served
# from it): its entry would have to depend on the value field's planes
_GROUPBY_ARGS = _CACHE_GROUPBY_ARGS | {"aggregate"}
_CACHE_ROWS_ARGS = frozenset({"_field", "field", "limit", "previous", "column"})


class _CacheCtx:
    """One call's cache context: the key, the referenced views, and the
    pre-execution version vector (None = uncacheable this round — the
    spec was eligible but the vector could not be assembled, e.g. a
    first sighting of an RPC-vector key or an unreachable peer)."""

    __slots__ = (
        "key", "kind", "views", "shard_list", "vector", "repair_spec",
        "dep_rows", "text", "index_name", "opt_remote", "call", "clocks",
        "hit", "hit_result",
    )

    def __init__(self, key, kind, views, shard_list, text, index_name,
                 repair_spec, dep_rows, opt_remote, call):
        self.key = key
        self.kind = kind
        self.views = views  # canonical sorted ((field, view), ...)
        self.shard_list = shard_list
        self.text = text
        self.index_name = index_name
        self.repair_spec = repair_spec
        self.dep_rows = dep_rows
        self.opt_remote = opt_remote
        self.call = call  # for per-node Shift shard-extension (distributed)
        self.vector = None
        self.clocks = None  # per-view mutation clocks, read pre-vector
        self.hit = False
        self.hit_result = None


def _group_by_launches() -> int:
    from pilosa_tpu.exec import groupby as qgb

    return qgb.STATS["kernel_tallies"] + qgb.STATS["xla_tallies"]


def _tag_group_by(launches: int, info: dict) -> None:
    """What one GroupBy's tallies did, on its exec.dispatch span (the
    caller is inside it and holds the dispatch mutex, so a delta of
    groupby.STATS is this query's): groupby.levels / live_groups /
    planes / fold_ms from `info`, groupby.tallies the launches."""
    sp = tracing.active_span()
    if sp is None:
        return
    for k, v in info.items():
        sp.set_tag(f"groupby.{k}", v)
    sp.set_tag("groupby.tallies", launches)


class Executor:
    """Single-node executor. Cluster fan-out wraps this via the same
    per-shard lowering (reference: executor.go:44)."""

    def __init__(self, holder: Holder):
        self.holder = holder

    # ------------------------------------------------------------------
    # entry
    # ------------------------------------------------------------------

    def execute(
        self,
        index_name: str,
        query: Union[str, Query],
        shards: Optional[Sequence[int]] = None,
        opt: Optional[ExecOptions] = None,
    ) -> List[Any]:
        return self.execute_response(index_name, query, shards, opt).results

    def execute_response(
        self,
        index_name: str,
        query: Union[str, Query],
        shards: Optional[Sequence[int]] = None,
        opt: Optional[ExecOptions] = None,
    ) -> QueryResponse:
        """Execute and return the full response incl. column attr sets when
        columnAttrs=true (reference: executor.go:113-205 Execute)."""
        # private copy: Options(columnAttrs=...) mutates opt mid-query (the
        # reference's shared-opt behavior) and must not leak to the caller
        opt = dataclasses.replace(opt) if opt is not None else ExecOptions()
        if isinstance(query, str):
            query = parse(query)
        idx = self.holder.index(index_name)
        if idx is None:
            raise NotFoundError(f"index not found: {index_name}")
        if query.write_call_n() > opt.max_writes:
            raise ExecError("too many writes in a single request")
        if shards is None:
            shards = opt.shards
        # key -> id translation (executor.go:2615 translateCalls); remote
        # (fan-out) requests arrive pre-translated by the coordinator.
        if not opt.remote:
            translation.translate_query(idx, query)
        results = []
        calls = query.calls
        cache_hits = 0
        i = 0
        while i < len(calls):
            # Batch maximal runs of adjacent Count calls into one multi-root
            # plan dispatch: shared operands are read from HBM once and the
            # per-dispatch fixed cost amortizes (~2x per-query at 4
            # counts/dispatch on v5e — the reference executes calls one by
            # one, executor.go:231).
            j = i
            while (
                j < len(calls)
                and calls[j].name == "Count"
                and len(calls[j].children) == 1
            ):
                j += 1
            if j - i >= 2 and self._counts_batchable(opt):
                # per-call result-cache interplay: the run is reads-only,
                # so every member's version vector can resolve up front;
                # cached members serve from host memory and only the
                # misses dispatch (whole-run batch when nothing hit)
                ctxs = [
                    self._cache_lookup(idx, cc, shards, opt)
                    for cc in calls[i:j]
                ]
                if any(cx is not None and cx.hit for cx in ctxs):
                    # serve the hits, and keep the MISSES batched: they
                    # are still adjacent Counts, so they ride one
                    # multi-root dispatch — one stale sibling must not
                    # degrade the other nine to per-call dispatches
                    miss = [
                        (cc, cx)
                        for cc, cx in zip(calls[i:j], ctxs)
                        if not (cx is not None and cx.hit)
                    ]
                    miss_results = None
                    if len(miss) >= 2:
                        miss_results = self._traced_count_batch(
                            idx, [cc for cc, _ in miss], shards, opt
                        )
                        if miss_results is not None:
                            for (_, cx), r in zip(miss, miss_results):
                                self._cache_store(idx, cx, r)
                    it = iter(miss_results or ())
                    for cc, cx in zip(calls[i:j], ctxs):
                        if cx is not None and cx.hit:
                            results.append(cx.hit_result)
                            cache_hits += 1
                        elif miss_results is not None:
                            results.append(next(it))
                        else:
                            r = self._traced_call(idx, cc, shards, opt)
                            self._cache_store(idx, cx, r)
                            results.append(r)
                    i = j
                    continue
                batch = self._traced_count_batch(idx, calls[i:j], shards, opt)
                if batch is not None:
                    for cx, r in zip(ctxs, batch):
                        self._cache_store(idx, cx, r)
                    results.extend(batch)
                else:
                    # no stacked form for some child: run the whole batch
                    # per-call (re-attempting ever-shorter batches would be
                    # O(run^2) lowering walks)
                    for cc, cx in zip(calls[i:j], ctxs):
                        r = self._traced_call(idx, cc, shards, opt)
                        self._cache_store(idx, cx, r)
                        results.append(r)
                i = j
                continue
            cx = self._cache_lookup(idx, calls[i], shards, opt)
            if cx is not None and cx.hit:
                results.append(cx.hit_result)
                cache_hits += 1
            else:
                r = self._traced_call(idx, calls[i], shards, opt)
                self._cache_store(idx, cx, r)
                results.append(r)
            i += 1
        if cache_hits:
            # flight-recorder attribution (a sub-millisecond p50 in the
            # histograms must be attributable, not mysterious): tag the
            # enclosing api.query span; profiles and the slow-query log
            # then show cache-served queries explicitly
            sp = tracing.active_span()
            if sp is not None:
                sp.set_tag("cache.hit", True)
                sp.set_tag("cache.hits", cache_hits)
        resp = QueryResponse(results=results)
        # Column attrs for every column in any Row result (executor.go:164;
        # Options(columnAttrs=...) mutates opt before we get here). Columns
        # excluded by excludeColumns have no segments, hence no attrs —
        # same interplay as the reference.
        if opt.column_attrs:
            cols: set = set()
            for r in results:
                if isinstance(r, Row):
                    cols.update(int(x) for x in r.columns().tolist())
            sets = []
            for col in sorted(cols):
                attrs = idx.column_attr_store.attrs(col)
                if attrs:
                    cas = ColumnAttrSet(id=col, attrs=attrs)
                    if idx.keys:
                        cas.key = idx.translate_store.key_for_id(col)
                        cas.id = 0
                    sets.append(cas)
            resp.column_attr_sets = sets
        # id -> key translation of results (executor.go:2786)
        if not opt.remote:
            resp.results = translation.translate_results(idx, query, results)
        return resp

    def _traced_call(self, idx: Index, c: Call, shards, opt: ExecOptions):
        """One top-level call under its exec.call span: what is in no
        child span (lowering, dispatch) is the call's own host work."""
        with tracing.start_span("exec.call") as sp:
            sp.set_tag("pql.family", c.name)
            return self._execute_call(idx, c, shards, opt)

    def _traced_count_batch(self, idx: Index, calls: List[Call], shards, opt):
        """A run of adjacent Counts as one multi-root plan: one exec.call
        span for the run."""
        with tracing.start_span("exec.call") as sp:
            sp.set_tag("pql.family", "Count")
            sp.set_tag("exec.calls", len(calls))
            return self._execute_count_batch(idx, calls, shards, opt)

    def _shards_for(self, idx: Index, shards, call: Optional[Call] = None) -> List[int]:
        if shards is not None:
            s = list(shards)
        else:
            s = sorted(idx.available_shards()) or [0]
        if call is not None:
            # Shift carries bits into following shards; materialize them even
            # when the index has no data there yet.
            k = self._count_shifts(call)
            if k:
                ext = set(s)
                for sh in s:
                    ext.update(range(sh + 1, sh + 1 + k))
                s = sorted(ext)
        return s

    # ------------------------------------------------------------------
    # versioned result cache (core/resultcache.py)
    # ------------------------------------------------------------------

    def _cache_spec(self, idx: Index, c: Call, shards, opt: ExecOptions):
        """Build the cache context for one call, or None when the call
        is ineligible (unknown shape, data-dependent views, attr reads).
        The key is (index scope token, canonical post-translation text,
        resolved shard list, remote flag): remote legs return different
        shapes (untrimmed TopN candidates) than coordinator results, so
        they cache under distinct keys."""
        kind = _cache_kind(c)
        if kind is None:
            return None
        scope = getattr(idx, "_cache_scope", None)
        if scope is None:
            return None
        views: List[Tuple[str, str]] = []
        repair_spec = None
        try:
            if kind == "count":
                if len(c.children) != 1 or c.args:
                    return None
                if not self._cache_views(idx, c.children[0], views):
                    return None
                repair_spec = self._cache_repair_spec(c.children[0])
            elif kind == "topn":
                if not set(c.args) <= _CACHE_TOPN_ARGS or len(c.children) > 1:
                    return None
                fname = c.args.get("_field")
                if not isinstance(fname, str):
                    return None
                f = idx.field(fname)
                if f is None or f.options.type == FIELD_TYPE_TIME:
                    return None
                views.append((fname, VIEW_STANDARD))
                for child in c.children:
                    if not self._cache_views(idx, child, views):
                        return None
            else:  # groupby
                if not set(c.args) <= _CACHE_GROUPBY_ARGS:
                    return None
                if not c.children:
                    return None
                for child in c.children:
                    if child.name != "Rows":
                        return None
                    if not set(child.args) <= _CACHE_ROWS_ARGS:
                        return None
                    fname = child.args.get("field") or child.args.get("_field")
                    if not isinstance(fname, str):
                        return None
                    f = idx.field(fname)
                    if f is None or f.options.type == FIELD_TYPE_TIME:
                        return None
                    views.append((fname, VIEW_STANDARD))
                filt = c.args.get("filter")
                if isinstance(filt, Call) and not self._cache_views(
                    idx, filt, views
                ):
                    return None
            shard_list = tuple(self._shards_for(idx, shards, c))
        except Exception:  # noqa: BLE001 - eligibility is best-effort
            return None
        uniq = tuple(sorted(set(views)))
        if not uniq:
            return None
        dep_rows = self._cache_dep_rows(idx, c, kind)
        text = str(c)
        key = (scope, text, shard_list, bool(opt.remote))
        return _CacheCtx(
            key, kind, uniq, shard_list, text, idx.name, repair_spec,
            dep_rows, bool(opt.remote), c,
        )

    def _cache_views(self, idx: Index, c: Call, out: list) -> bool:
        """Collect the (field, view) pairs a bitmap tree reads; False
        when they are not statically enumerable (time-quantum ranges,
        TIME fields whose view set depends on data bounds, unknown call
        shapes)."""
        if any(k in c.args for k in _CACHE_TIME_ARGS):
            return False
        name = c.name
        if name in ("Union", "Intersect", "Difference", "Xor", "Shift"):
            pass
        elif name in ("Not", "All"):
            ef = idx.existence_field()
            if ef is None:
                return False
            out.append((ef.name, VIEW_STANDARD))
        elif name in ("Row", "Range"):
            conds = c.condition_args()
            if conds:
                if len(c.args) != 1 or len(conds) != 1 or c.children:
                    return False
                fname = next(iter(conds))
                f = idx.field(fname)
                if f is None or f.options.type == FIELD_TYPE_TIME:
                    return False
                out.append((fname, f.bsi_view_name()))
                return True
            args = [k for k in c.args if not k.startswith("_")]
            if len(args) != 1 or c.children:
                return False
            fname = args[0]
            rid = c.args[fname]
            if isinstance(rid, bool) or not isinstance(rid, int):
                return False  # untranslated key / call arg: let exec decide
            f = idx.field(fname)
            if f is None or f.options.type == FIELD_TYPE_TIME:
                return False
            out.append((fname, VIEW_STANDARD))
            return True
        else:
            return False
        for child in c.children:
            if not self._cache_views(idx, child, out):
                return False
        for v in c.args.values():
            if isinstance(v, Call) and not self._cache_views(idx, v, out):
                return False
        return True

    # monotone-tree repair leaf cap: op_popcount over the patch words is
    # O(leaves × changed words) host work per merged shard — past a few
    # operands a recompute through the normal dispatch path wins anyway
    _REPAIR_MAX_LEAVES = 8

    @staticmethod
    def _repair_leaf(c: Call) -> Optional[Tuple[str, str, int]]:
        """A plain translated Row(field=rid) — the only repairable leaf
        shape (BSI conditions and keyed rows read state the word delta
        does not carry)."""
        if c.name != "Row" or c.children or c.condition_args():
            return None
        args = [k for k in c.args if not k.startswith("_")]
        if len(args) != 1:
            return None
        rid = c.args[args[0]]
        if isinstance(rid, bool) or not isinstance(rid, int):
            return None
        return (args[0], VIEW_STANDARD, rid)

    @classmethod
    def _cache_repair_spec(cls, c: Call):
        """Count over a pure Intersect/Union tree of plain Rows (or one
        Row) is monotone-repairable: for set-only bursts the merge
        barrier's word deltas recompute `popcount(op(leaves))` over just
        the changed word indexes, and the telescoped per-shard delta
        patches the cached total in place (core/resultcache.py). Mixed
        nesting, Difference/Xor, BSI and Not fall back to
        revalidate-or-recompute. Returns ("and"|"or", (leaf, ...))."""
        lf = cls._repair_leaf(c)
        if lf is not None:
            return ("and", (lf,))
        if c.name not in ("Intersect", "Union") or c.args:
            return None
        if not 2 <= len(c.children) <= cls._REPAIR_MAX_LEAVES:
            return None
        leaves = []
        for ch in c.children:
            lf = cls._repair_leaf(ch)
            if lf is None:
                return None
            leaves.append(lf)
        return ("and" if c.name == "Intersect" else "or", tuple(leaves))

    def _cache_dep_rows(self, idx: Index, c: Call, kind: str):
        """Row-level dependency map for structural re-key:
        {(field, view): frozenset(row_ids) | None}, where None means the
        entry depends on ALL rows of that view (existence walks, BSI
        planes, TopN/GroupBy tally scans). A merge burst that provably
        touched no depended-on row of its view re-keys the entry to the
        merged versions without recompute (core/resultcache.py). Missing
        views behave as None on the cache side, so a partial map is
        safe — but the walk mirrors _cache_views, which already gated
        every shape that can reach here."""
        deps: Dict[Tuple[str, str], Optional[set]] = {}

        def dep_all(fname: str, vname: str) -> None:
            deps[(fname, vname)] = None

        def dep_row(fname: str, vname: str, rid: int) -> None:
            cur = deps.get((fname, vname), set())
            if cur is not None:
                cur.add(rid)
                deps[(fname, vname)] = cur

        def walk(call: Call) -> None:
            lf = self._repair_leaf(call)
            if lf is not None:
                dep_row(*lf)
                return
            if call.name in ("Row", "Range"):
                conds = call.condition_args()
                fname = next(iter(conds)) if conds else None
                f = idx.field(fname) if fname else None
                dep_all(fname, f.bsi_view_name() if f is not None else "")
                return
            if call.name in ("Not", "All"):
                ef = idx.existence_field()
                dep_all(ef.name if ef is not None else "", VIEW_STANDARD)
            for child in call.children:
                walk(child)
            for v in call.args.values():
                if isinstance(v, Call):
                    walk(v)

        try:
            if kind == "count":
                walk(c.children[0])
            elif kind == "topn":
                # the tally scan reads every row of the main field
                dep_all(c.args["_field"], VIEW_STANDARD)
                for child in c.children:
                    walk(child)
            else:  # groupby: each Rows() enumerates all rows of its field
                for child in c.children:
                    fname = child.args.get("field") or child.args.get("_field")
                    dep_all(fname, VIEW_STANDARD)
                filt = c.args.get("filter")
                if isinstance(filt, Call):
                    walk(filt)
        except Exception:  # noqa: BLE001 - dep map is an optimization only
            return None
        if not deps:
            return None
        return {
            k: (frozenset(v) if v is not None else None)
            for k, v in deps.items()
        }

    def local_version_vector(
        self, idx: Index, views, shard_list, node: str = ""
    ) -> tuple:
        """The exact fragment-version vector this node would read for
        `views` over `shard_list` — lock-free monotonic reads (every
        mutation funnel bumps Fragment.version, staged writes included).
        Elements carry the View's instance token so a delete/recreate
        can never alias an old entry back to life."""
        vec = []
        for fname, vname in views:
            f = idx.field(fname)
            if f is None:
                vec.append(("m", node, fname, ""))
                continue
            v = f.view(vname)
            if v is None:
                vec.append(("m", node, fname, vname))
                continue
            # hot loop (954 iterations per view on the bench geometry):
            # one local dict ref + .get per shard, no method dispatch
            frags = v.fragments
            versions = tuple(
                fr.version if (fr := frags.get(s)) is not None else -1
                for s in shard_list
            )
            vec.append(
                ("v", node, fname, vname, v._stack_token,
                 tuple(shard_list), versions)
            )
        return tuple(vec)

    def version_vector(
        self, idx: Index, ctx: _CacheCtx, opt: ExecOptions, expect=None
    ):
        """Single-node: the local vector IS the vector. The distributed
        executor overrides this with the fan-out's assembled vector
        (local + in-process mesh members + remote peers). `expect` is
        the store-path fast-fail hint: when the in-process parts
        already diverge from it, assembly may bail (None) without
        paying the remote version RPCs for a store that cannot
        happen — local collection is cheap, so the base class ignores
        it."""
        return self.local_version_vector(idx, ctx.views, ctx.shard_list)

    def clock_vector(self, idx: Index, ctx: _CacheCtx, opt: ExecOptions):
        """O(#views) revalidation fast path: one mutation-clock integer
        per referenced view (View.mutation_clock — bumped on every
        mutation event that bumps a fragment version). Clock-equal
        implies version-vector-equal, so the warm path never walks the
        shard axis. None disables the fast path (the distributed
        coordinator's entries span remote nodes whose clocks live
        behind an RPC that dominates anyway)."""
        vec = []
        for fname, vname in ctx.views:
            f = idx.field(fname)
            if f is None:
                vec.append(("m", "", fname, ""))
                continue
            v = f.view(vname)
            if v is None:
                vec.append(("m", "", fname, vname))
                continue
            vec.append(("c", v._stack_token, v.mutation_clock))
        return tuple(vec)

    def _cache_lookup(self, idx: Index, c: Call, shards, opt: ExecOptions):
        """Resolve one call against the result cache. Returns None when
        the call is ineligible; otherwise a _CacheCtx whose `hit` is set
        when the stored result revalidated (or was repaired in place by
        the read barrier this lookup ran)."""
        if _cache_kind(c) is None:
            return None  # nothing to look up, and no span for it
        with tracing.start_span("exec.cache") as sp:
            sp.set_tag("cache.op", "lookup")
            ctx = self._cache_resolve(idx, c, shards, opt)
            sp.set_tag("cache.hit", ctx is not None and ctx.hit)
            return ctx

    def _cache_resolve(self, idx: Index, c: Call, shards, opt: ExecOptions):
        ctx = self._cache_spec(idx, c, shards, opt)
        if ctx is None:
            return None
        RC = rcache.RESULT_CACHE
        # clock fast path: clocks are read BEFORE any vector they might
        # arm, so a write racing the reads keeps the fast path disarmed
        # (live clock moved past) instead of ever serving stale
        clocks = ctx.clocks = self.clock_vector(idx, ctx, opt)
        found, res = RC.get_by_clock(ctx.key, clocks)
        if found:
            ctx.hit = True
            ctx.hit_result = res
            return ctx
        ctx.vector = self.version_vector(idx, ctx, opt)
        if ctx.vector is None:
            # unassemblable vector (first sighting of an RPC key, an
            # unreachable peer): a lookup happened and nothing served —
            # that is a miss on the dashboards, per observability.md
            RC.count_miss()
            return ctx
        # miss accounting is deferred to the END of the lookup: a
        # repaired serve is one hit, not a miss-then-hit (the repair
        # retry would otherwise pin cacheHitRate at 0.5 on a fully
        # cache-served dashboard)
        found, res = RC.get(ctx.key, ctx.vector, recount=False)
        if found:
            RC.refresh_clocks(ctx.key, clocks)
        elif (
            ctx.repair_spec is not None or ctx.dep_rows is not None
        ) and RC.repairable(ctx.key):
            # cheap repair: collect the current versions UNDER the read
            # barrier — sync_pending runs the merge barrier, which fires
            # note_merges and patches the cached Count from the burst's
            # word delta (count += popcount(delta & ~old)); if the entry
            # re-keyed to the live versions, serve it with zero
            # dispatches and zero operand re-reads
            clocks = ctx.clocks = self.clock_vector(idx, ctx, opt)
            self._cache_barrier(idx, ctx)
            vec2 = self.version_vector(idx, ctx, opt)
            if vec2 is not None:
                found, res = RC.get(ctx.key, vec2, recount=False)
                ctx.vector = vec2
                if found:
                    RC.refresh_clocks(ctx.key, clocks)
        if found:
            ctx.hit = True
            ctx.hit_result = res
        else:
            RC.count_miss()
        return ctx

    def _cache_barrier(self, idx: Index, ctx: _CacheCtx) -> None:
        """Run the read barrier over the call's referenced views (the
        same barrier execution would run first) so staged bursts merge
        and the repair hook fires."""
        for fname, vname in ctx.views:
            f = idx.field(fname)
            v = f.view(vname) if f is not None else None
            if v is not None:
                try:
                    v.sync_pending(shards=ctx.shard_list)
                except Exception:  # noqa: BLE001 - barrier is best-effort here
                    return

    def _cache_store(self, idx: Index, ctx, result) -> None:
        """Store a freshly computed result, guarded against racing
        writers: the vector is re-collected AFTER execution and the
        entry is stored only when it equals the pre-execution one —
        execution itself never bumps versions (barriers merge, stage
        bumps already happened), so inequality means a concurrent
        mutation landed mid-query and the result belongs to no single
        version state."""
        if ctx is None or ctx.vector is None or result is None:
            return
        with tracing.start_span("exec.cache") as sp:
            sp.set_tag("cache.op", "store")
            opt = ExecOptions(remote=ctx.opt_remote)
            vec2 = self.version_vector(idx, ctx, opt, expect=ctx.vector)
            if vec2 != ctx.vector:
                return
            rcache.RESULT_CACHE.put(
                ctx.key, ctx.kind, ctx.index_name, ctx.text, result,
                ctx.vector, repair_spec=ctx.repair_spec,
                dep_rows=ctx.dep_rows, clocks=ctx.clocks,
            )

    # ------------------------------------------------------------------
    # prefetch warming (pilosa_tpu/hbm/)
    # ------------------------------------------------------------------

    _WARM_BITMAP = frozenset(
        {"Row", "Range", "Union", "Intersect", "Difference", "Xor", "Not",
         "All", "Shift"}
    )

    def warm(self, index_name: str, query, shards=None) -> int:
        """Stage a query's operand extents WITHOUT dispatching — the
        prefetch path (hbm/prefetch.py). Dispatches serialize behind
        plan._DISPATCH_MU but host->device staging does not, so a queued
        query's extents ride PCIe while the current dispatch runs.

        Best-effort by contract: every failure is swallowed (a warm miss
        costs only the staging the real query would do anyway), the query
        is deep-copied before translation (the admission-held original
        must not be mutated), and nothing is pinned past this call.
        Returns the number of call trees warmed (introspection/tests)."""
        import copy

        warmed = 0
        try:
            idx = self.holder.index(index_name)
            if idx is None:
                return 0
            q = (
                copy.deepcopy(query)
                if isinstance(query, Query)
                else parse(str(query))
            )
            translation.translate_query(idx, q)
            for c in q.calls:
                child = None
                if c.name == "Count" and len(c.children) == 1:
                    child = c.children[0]
                elif c.name in self._WARM_BITMAP:
                    child = c
                if child is None:
                    continue
                try:
                    shard_list = self._shards_for(idx, shards, child)
                    plans = self._lower_plans(idx, child, shard_list)
                except Exception:  # noqa: BLE001 - warming is best-effort
                    continue
                if plans:
                    for sp in plans:
                        sp.release_extents()
                    warmed += 1
        except Exception:  # noqa: BLE001 - warming must never raise
            pass
        return warmed

    # ------------------------------------------------------------------
    # dispatch (executor.go:274)
    # ------------------------------------------------------------------

    def _execute_call(self, idx: Index, c: Call, shards, opt: ExecOptions):
        name = c.name
        if name not in ("Set", "Clear", "SetRowAttrs", "SetColumnAttrs", "Options"):
            shards = self._shards_for(idx, shards, c)
        if name == "Sum":
            return self._execute_sum(idx, c, shards)
        if name == "Min":
            return self._execute_min_max(idx, c, shards, is_min=True)
        if name == "Max":
            return self._execute_min_max(idx, c, shards, is_min=False)
        if name == "MinRow":
            return self._execute_min_max_row(idx, c, shards, is_min=True)
        if name == "MaxRow":
            return self._execute_min_max_row(idx, c, shards, is_min=False)
        if name == "Clear":
            return self._execute_clear(idx, c)
        if name == "ClearRow":
            return self._execute_clear_row(idx, c, shards)
        if name == "Store":
            return self._execute_store(idx, c, shards)
        if name == "Count":
            return self._execute_count(idx, c, shards)
        if name == "Set":
            return self._execute_set(idx, c)
        if name == "SetRowAttrs":
            self._execute_set_row_attrs(idx, c)
            return None
        if name == "SetColumnAttrs":
            self._execute_set_column_attrs(idx, c)
            return None
        if name == "TopN":
            return self._execute_topn(idx, c, shards, opt)
        if name == "Rows":
            return self._execute_rows(idx, c, shards)
        if name == "GroupBy":
            return self._execute_group_by(idx, c, shards)
        if name == "Options":
            return self._execute_options(idx, c, shards, opt)
        return self._execute_bitmap_call(idx, c, shards, opt)

    # ------------------------------------------------------------------
    # bitmap calls
    # ------------------------------------------------------------------

    def _count_shifts(self, c: Call) -> int:
        n = 1 if c.name == "Shift" else 0
        n += sum(self._count_shifts(ch) for ch in c.children)
        n += sum(self._count_shifts(v) for v in c.args.values() if isinstance(v, Call))
        return n

    def _lower_stacked(self, idx: Index, c: Call, shard_list) -> Optional[StackedPlan]:
        """Try to lower a bitmap call tree to one compiled stacked plan
        (exec/plan.py; VERDICT round-1 task: the mesh IS the executor).
        Returns None when the call shape has no stacked form — the caller
        falls back to the per-shard loop. Semantic ExecErrors propagate.

        Sparse views (SparseView guard) re-lower over a COMPACTED shard
        list — only shards where some touched view is materialized, plus
        Shift relay successors — keeping the one-dispatch property while
        sparse shards stay free (reference: field.go:263-296)."""
        try:
            lowered = self._lower_roots(idx, [c], shard_list)
        except BudgetExceeded:
            return None  # callers that can chunk use _lower_plans instead
        if lowered is None:
            return None
        roots, low, n_out, out_shards = lowered
        return StackedPlan(
            roots[0], low.operands, low.scalars, n_out, out_shards,
            extents=low.extents,
        )

    def _lower_plans(self, idx: Index, c: Call, shard_list) -> Optional[List[StackedPlan]]:
        """One stacked plan when the operands fit the device budget; a
        handful of shard-axis-chunked plans when they don't (recursive
        halving) — NEVER the dispatch-per-shard loop just because the index
        is big. Returns None only for genuinely unsupported shapes."""
        if not _STACKED_ENABLED or not shard_list:
            return None

        def one(chunk):
            lowered = self._lower_roots(idx, [c], chunk, empty_ok=True)
            if lowered is None:
                return None
            if lowered == self._EMPTY_LOWER:
                return []
            roots, low, n_out, out_shards = lowered
            return [
                StackedPlan(
                    roots[0], low.operands, low.scalars, n_out, out_shards,
                    extents=low.extents,
                )
            ]

        return self._chunk_by_budget(list(shard_list), one)

    @staticmethod
    def _release_chunk_extents(items) -> None:
        """Unpin the extent tables of lowered-but-abandoned chunk results
        (plans carry one; BSI operand tuples already released theirs)."""
        for it in items or ():
            rel = getattr(it, "release_extents", None)
            if rel is not None:
                rel()

    @staticmethod
    def _chunk_by_budget(shard_list, lower_one):
        """Shared recursive halving for budget-exceeded lowering:
        lower_one(chunk) returns a list of per-chunk results ([] = empty
        range) or None for genuinely unsupported shapes; BudgetExceeded
        splits the shard axis until chunks fit (or bottoms out below 16
        shards, where the per-shard fallback takes over). A half that
        fails must not abandon the other half's lowered plans with their
        extent pins still held."""
        try:
            return lower_one(shard_list)
        except BudgetExceeded:
            if len(shard_list) < 16:
                return None  # can't subdivide usefully: per-shard fallback
            mid = len(shard_list) // 2
            left = Executor._chunk_by_budget(shard_list[:mid], lower_one)
            try:
                right = Executor._chunk_by_budget(shard_list[mid:], lower_one)
            except BaseException:
                Executor._release_chunk_extents(left)
                raise
            if left is None or right is None:
                Executor._release_chunk_extents(left)
                Executor._release_chunk_extents(right)
                return None
            return left + right

    _EMPTY_LOWER = "empty"  # sentinel: nothing materialized in this range

    def _lower_roots(self, idx: Index, calls: List[Call], shard_list, empty_ok: bool = False):
        """Lower one or more bitmap call trees over ONE shared operand set
        (shared leaf memo: an operand referenced by several calls is
        materialized once). Returns (roots, lowering, n_out, out_shards),
        None for per-shard fallback, or (with empty_ok) the _EMPTY_LOWER
        sentinel when no operand is materialized anywhere in the range;
        semantic ExecErrors propagate, BudgetExceeded propagates for
        shard-axis chunking."""
        if not _STACKED_ENABLED or not shard_list:
            return None
        with lower_span("stacked"):
            return self._lower_roots_impl(idx, calls, shard_list, empty_ok)

    def _lower_roots_impl(self, idx: Index, calls: List[Call], shard_list, empty_ok: bool):
        shard_list = list(shard_list)
        # Shift reads the PREVIOUS shard's child bits for its carry
        # (serial path: _bitmap_call_shard(shard-1)); when the caller asked
        # for an explicit shard subset, those predecessors may hold data but
        # be absent from the list. Append them to the stack (depth-k shifts
        # need k predecessors); output trimming excludes them.
        k = max(self._count_shifts(c) for c in calls)
        if k:
            present = set(shard_list)
            extra = []
            for s in shard_list:
                for p in range(max(0, s - k), s):
                    if p not in present:
                        present.add(p)
                        extra.append(p)
            aug = shard_list + sorted(extra)
        else:
            aug = shard_list
        from pilosa_tpu.core.devcache import DEVICE_CACHE

        low = _StackedLowering(self, idx, aug)
        try:
            # defer budget eviction across this query's operand staging:
            # making room for operand K by evicting operand K+1's extents
            # (LRU's cyclic-scan cascade) would re-upload the whole
            # working set every query (core/devcache.py deferred_eviction)
            with DEVICE_CACHE.deferred_eviction():
                roots = [low.lower(c) for c in calls]
        except SparseView:
            low.extents.release()
            return self._lower_roots_compacted(idx, calls, shard_list, aug, k)
        except BudgetExceeded:
            low.extents.release()
            raise  # recoverable by shard-axis chunking (_lower_plans)
        except Unsupported:
            low.extents.release()
            return None
        except BaseException:
            low.extents.release()  # semantic ExecErrors etc. propagate
            raise
        if not low.operands:
            # nothing materialized anywhere: trivial (empty) result
            low.extents.release()
            return self._EMPTY_LOWER if empty_ok else None
        return roots, low, len(shard_list), shard_list

    def _lower_roots_compacted(
        self, idx: Index, calls: List[Call], shard_list, aug, k: int
    ):
        """SparseView recovery: collect the views the trees touch (cheap
        no-stack walk), keep only shards where any of them is materialized
        (plus up-to-k Shift relay successors, which forward carries across
        gaps), and re-lower over that compacted list."""
        collect = _StackedLowering(self, idx, aug, collect=True)
        try:
            for c in calls:
                collect.lower(c)
        except Unsupported:
            return None
        views = list(collect.views.values())
        keep = {
            s
            for s in aug
            if any(v.fragment_if_exists(s) is not None for v in views)
        }
        if k:
            aug_set = set(aug)
            for s in sorted(keep):
                for t in range(s + 1, s + 1 + k):
                    if t in aug_set:
                        keep.add(t)
        compact = [s for s in aug if s in keep]
        if not compact:
            return None  # nothing anywhere: the serial loop is all-None
        req = set(shard_list)
        n_out = sum(1 for s in compact if s in req)
        from pilosa_tpu.core.devcache import DEVICE_CACHE

        low = _StackedLowering(self, idx, compact, no_sparse_guard=True)
        try:
            with DEVICE_CACHE.deferred_eviction():
                roots = [low.lower(c) for c in calls]
        except BudgetExceeded:
            low.extents.release()
            raise  # recoverable by shard-axis chunking (_lower_plans)
        except Unsupported:
            low.extents.release()
            return None
        except BaseException:
            low.extents.release()
            raise
        if not low.operands:
            low.extents.release()
            return None
        # requested shards precede the aug extras in `compact`, so the
        # first n_out positions are exactly the kept requested shards
        return roots, low, n_out, compact[:n_out]

    def _execute_bitmap_call(
        self, idx: Index, c: Call, shards, opt: Optional[ExecOptions] = None
    ) -> Row:
        shard_list = self._shards_for(idx, shards)
        plans = self._lower_plans(idx, c, shard_list)
        if plans is not None:
            segments = {}
            try:
                for sp in plans:
                    stack = np.asarray(sp.rows())
                    for i, shard in enumerate(sp.out_shards):
                        if stack[i].any():
                            # copy: a slice view would pin the whole [S, W] stack
                            segments[shard] = stack[i].copy()
            finally:
                # a failing chunk must not leave later chunks' extents pinned
                for sp in plans:
                    sp.release_extents()
            return self._finish_bitmap_row(idx, c, Row(segments), opt)
        segments = {}
        memo: dict = {}
        for shard in shard_list:
            words = self._bitmap_call_shard(idx, c, shard, memo)
            if words is not None:
                segments[shard] = words
        return self._finish_bitmap_row(idx, c, Row(segments), opt)

    def _finish_bitmap_row(
        self, idx: Index, c: Call, row: Row, opt: Optional[ExecOptions]
    ) -> Row:
        """Attach row attrs to plain Row() results and honor
        excludeRowAttrs/excludeColumns (reference: executor.go:595-647
        executeBitmapCall tail; runs on the coordinator only — remote
        fan-out partials are merged and re-finished there)."""
        if opt is None or opt.remote:
            return row
        if c.name == "Row" and not any(
            isinstance(v, Condition) for v in c.args.values()
        ):
            if opt.exclude_row_attrs:
                row.attrs = {}
            else:
                fname = next(
                    (
                        k
                        for k in c.args
                        if not k.startswith("_") and k not in ("from", "to")
                    ),
                    None,
                )
                f = idx.field(fname) if fname else None
                if f is not None:
                    rid = c.args.get(fname)
                    if isinstance(rid, (int, np.integer)) and not isinstance(
                        rid, bool
                    ):
                        row.attrs = f.row_attr_store.attrs(int(rid))
        if opt.exclude_columns:
            row.segments = {}
        return row

    def _bitmap_call_shard(self, idx: Index, c: Call, shard: int, memo=None):
        """Lower one bitmap call for one shard to device words (or None).

        `memo` caches (call, shard) -> words within one query execution so a
        call subtree referenced twice (e.g. by Shift's cross-shard carry) is
        lowered once."""
        if memo is not None:
            key = (id(c), shard)
            if key in memo:
                return memo[key]
        words = self._bitmap_call_shard_uncached(idx, c, shard, memo)
        if memo is not None:
            memo[(id(c), shard)] = words
        return words

    # dispatch-ok escapes below: per-shard fallback path — single-device
    # row arrays (fragment.row_device), no mesh sharding, no collectives
    # to rendezvous
    def _bitmap_call_shard_uncached(  # dispatch-ok: per-shard path, single-device
        self, idx: Index, c: Call, shard: int, memo=None
    ):
        name = c.name
        if name in ("Row", "Range"):
            return self._row_shard(idx, c, shard)
        if name == "Intersect":
            return self._nary_shard(idx, c, shard, "intersect", memo)
        if name == "Union":
            return self._nary_shard(idx, c, shard, "union", memo)
        if name == "Difference":
            return self._nary_shard(idx, c, shard, "difference", memo)
        if name == "Xor":
            return self._nary_shard(idx, c, shard, "xor", memo)
        if name == "Not":
            return self._not_shard(idx, c, shard, memo)
        if name == "Shift":
            # Shift crosses shard boundaries: this shard's result is its own
            # child bits shifted up, OR'd with the overflow carried out of the
            # previous shard's child bits — composable per shard, so Shift
            # works nested inside any other call.
            if len(c.children) != 1:
                raise ExecError("Shift() requires a single bitmap input")
            n = c.int_arg("n")
            n = 1 if n is None else n
            cur = self._bitmap_call_shard(idx, c.children[0], shard, memo)
            out = None
            if cur is not None:
                out, _ = ob.shift_bits(cur, n)
            if shard > 0:
                prev = self._bitmap_call_shard(idx, c.children[0], shard - 1, memo)
                if prev is not None:
                    _, carry = ob.shift_bits(prev, n)
                    out = carry if out is None else ob.b_or(out, carry)
            return out
        if name == "All":
            return self._existence_words(idx, shard)
        raise ExecError(f"unknown call: {name}")

    def _nary_shard(  # dispatch-ok: per-shard path, single-device
        self, idx: Index, c: Call, shard: int, op: str, memo=None
    ):
        if not c.children:
            if op == "intersect":
                raise ExecError("empty Intersect query is currently not supported")
            return None
        words = [self._bitmap_call_shard(idx, ch, shard, memo) for ch in c.children]
        zero = None
        if op == "intersect":
            if any(w is None for w in words):
                return None
            out = words[0]
            for w in words[1:]:
                out = ob.b_and(out, w)
            return out
        if op == "union":
            present = [w for w in words if w is not None]
            if not present:
                return None
            out = present[0]
            for w in present[1:]:
                out = ob.b_or(out, w)
            return out
        if op == "difference":
            out = words[0]
            if out is None:
                return None
            for w in words[1:]:
                if w is not None:
                    out = ob.b_andnot(out, w)
            return out
        if op == "xor":
            present = [w for w in words if w is not None]
            if not present:
                return None
            out = present[0]
            for w in present[1:]:
                out = ob.b_xor(out, w)
            return out
        raise AssertionError(op)

    def _not_shard(  # dispatch-ok: per-shard path, single-device
        self, idx: Index, c: Call, shard: int, memo=None
    ):
        """Not via the existence field (executor.go:1734 executeNot)."""
        if not idx.track_existence:
            raise ExecError("Not() query requires existence tracking to be enabled")
        if len(c.children) != 1:
            raise ExecError("Not() requires a single bitmap input")
        exists = self._existence_words(idx, shard)
        if exists is None:
            return None
        child = self._bitmap_call_shard(idx, c.children[0], shard, memo)
        if child is None:
            return exists
        return ob.b_andnot(exists, child)

    def _existence_words(self, idx: Index, shard: int):
        ef = idx.existence_field()
        if ef is None:
            raise ExecError("existence field not available")
        v = ef.view(VIEW_STANDARD)
        if v is None:
            return None
        frag = v.fragment_if_exists(shard)
        return None if frag is None else frag.row_device(0)

    # -- Row / Range -------------------------------------------------------

    def _field_of(self, idx: Index, name: str) -> Field:
        f = idx.field(name)
        if f is None:
            raise NotFoundError(f"field not found: {name}")
        return f

    def _row_shard(  # dispatch-ok: per-shard path, single-device
        self, idx: Index, c: Call, shard: int
    ):
        if c.has_conditions():
            return self._row_bsi_shard(idx, c, shard)
        field_name = self._field_arg_name(c)
        f = self._field_of(idx, field_name)
        row_id = c.args.get(field_name)
        if isinstance(row_id, bool):
            if f.options.type != FIELD_TYPE_BOOL:
                raise ExecError("Row() bool value requires a bool field")
            row_id = 1 if row_id else 0
        if not isinstance(row_id, int):
            if isinstance(row_id, str):
                raise ExecError(
                    f"string row key {row_id!r} requires field keys (translation)"
                )
            raise ExecError("Row() must specify a row")
        if f.options.type == FIELD_TYPE_BOOL and row_id not in (0, 1):
            raise ExecError("Row() bool field expects row 0 or 1")

        from_arg = c.args.get("from")
        to_arg = c.args.get("to")
        if from_arg is None and to_arg is None:
            v = f.view(VIEW_STANDARD)
            if v is None:
                return None
            frag = v.fragment_if_exists(shard)
            return None if frag is None else frag.row_device(row_id)

        # time range (executor.go executeRowShard from/to handling)
        if f.options.type != FIELD_TYPE_TIME:
            raise ExecError(f"field {field_name} is not a time field")
        quantum = f.options.time_quantum
        from_t = timeq.parse_time(from_arg) if from_arg is not None else None
        to_t = timeq.parse_time(to_arg) if to_arg is not None else None
        if from_t is None or to_t is None:
            lo, hi = self._field_time_bounds(f)
            if lo is None:
                return None
            from_t = from_t or lo
            to_t = to_t or hi
        out = None
        for vname in timeq.views_by_time_range(VIEW_STANDARD, from_t, to_t, quantum):
            v = f.view(vname)
            if v is None:
                continue
            frag = v.fragment_if_exists(shard)
            if frag is None:
                continue
            w = frag.row_device(row_id)
            out = w if out is None else ob.b_or(out, w)
        return out

    def _field_time_bounds(self, f: Field):
        """Min/max time covered by the field's existing time views."""
        return timeq.min_max_view_times(f.views.keys(), f.options.time_quantum)

    def _field_arg_name(self, c: Call) -> str:
        for k in c.args:
            if not k.startswith("_") and k not in ("from", "to"):
                return k
        raise ExecError(f"{c.name}() argument required: field")

    def _row_bsi_shard(self, idx: Index, c: Call, shard: int):
        """BSI condition row (executor.go:1533 executeRowBSIGroupShard)."""
        conds = c.condition_args()
        if len(c.args) != 1 or len(conds) != 1:
            raise ExecError("Row(): exactly one condition required")
        field_name, cond = next(iter(conds.items()))
        f = self._field_of(idx, field_name)
        if f.options.type != FIELD_TYPE_INT:
            raise ExecError(f"field {field_name} is not an int field")
        o = f.options
        bsiv = f.view(f.bsi_view_name())
        if bsiv is None:
            return None
        frag = bsiv.fragment_if_exists(shard)
        if frag is None:
            return None

        if cond.op == NEQ and cond.value is None:  # != null
            return frag.not_null()
        if cond.op == BETWEEN:
            lo, hi = cond.int_pair()
            blo, bhi, out_of_range = f.base_value_between(lo, hi)
            if out_of_range:
                return None
            if lo <= o.min and hi >= o.max:
                return frag.not_null()
            return frag.range_between(o.bit_depth, blo, bhi)

        if not isinstance(cond.value, int) or isinstance(cond.value, bool):
            raise ExecError("Row(): conditions only support integer values")
        value = cond.value
        op = _COND_OP_NAME[cond.op]
        base_value, out_of_range = f.base_value(op, value)
        if out_of_range and cond.op != NEQ:
            return None
        # full-range saturation -> notNull
        if (
            (cond.op == LT and value > o.max)
            or (cond.op == LTE and value >= o.max)
            or (cond.op == GT and value < o.min)
            or (cond.op == GTE and value <= o.min)
        ):
            return frag.not_null()
        if out_of_range and cond.op == NEQ:
            return frag.not_null()
        return frag.range_op(op, o.bit_depth, base_value)

    # ------------------------------------------------------------------
    # Count / Sum / Min / Max
    # ------------------------------------------------------------------

    def _counts_batchable(self, opt: ExecOptions) -> bool:
        """Whether multi-Count batching may run locally (the distributed
        executor restricts it to remote/single-node execution, where the
        shard list is already this node's responsibility)."""
        return True

    def _execute_count_batch(
        self, idx: Index, calls: List[Call], shards, opt: Optional[ExecOptions] = None
    ) -> Optional[List[int]]:
        """N adjacent Count calls as ONE multi-root dispatch + one [N, S]
        host read. Returns None (caller falls back to per-call execution)
        when any child has no stacked form. `opt` lets the distributed
        override distinguish remote legs (local lowering) from
        coordinator-side batches (mesh-group lowering or per-call
        fan-out); the local path ignores it."""
        children = []
        for c in calls:
            if len(c.children) != 1:
                raise ExecError("Count() only accepts a single bitmap input")
            children.append(c.children[0])
        # every call must agree on its shard list (Shift calls extend
        # theirs with successor shards): evaluating one call over another's
        # extension would diverge from per-call execution on explicit
        # shard subsets
        lists = [self._shards_for(idx, shards, c) for c in calls]
        if any(lst != lists[0] for lst in lists[1:]):
            return None
        try:
            lowered = self._lower_roots(idx, children, lists[0])
        except BudgetExceeded:
            # per-call execution chunks each count by shard axis instead
            return None
        if lowered is None:
            return None
        roots, low, n_out, out_shards = lowered
        mp = MultiCountPlan(
            roots, low.operands, low.scalars, n_out, out_shards,
            extents=low.extents,
        )
        return mp.counts()

    def _execute_count(self, idx: Index, c: Call, shards) -> int:
        if len(c.children) != 1:
            raise ExecError("Count() only accepts a single bitmap input")
        shard_list = self._shards_for(idx, shards)
        child = c.children[0]
        if child.name in ("Row", "Range") and child.has_conditions():
            # single-BSI-condition counts ride the plane-streamed ladders
            # (exec/bsistream.py): slab-bounded plane residency, one
            # dispatch per slab, scalar halfword-pair reads — instead of
            # materializing the whole [D, S, W] stack through a plan
            from pilosa_tpu.exec import bsistream

            streamed = bsistream.count_range(self, idx, child, shard_list)
            if streamed is not None:
                return streamed
        plans = self._lower_plans(idx, child, shard_list)
        if plans is not None:
            # one jitted dispatch + one [S] host read per (budget-sized)
            # shard chunk — usually exactly one
            try:
                return sum(sp.count() for sp in plans)
            finally:
                for sp in plans:
                    sp.release_extents()
        # Per-shard fallback: the algebra still lowers shard-by-shard, but
        # counts are fetched in fused chunked reads (one [G] transfer per
        # _FALLBACK_READ_CHUNK shards) instead of one host sync per shard:
        # dispatches pipeline, each blocking read is a synchronisation
        # (VERDICT r2 #8; the pattern of the fused BSI aggregate read).
        total = 0
        memo: dict = {}
        pend: list = []
        for shard in shard_list:
            words = self._bitmap_call_shard(idx, c.children[0], shard, memo)
            if words is not None:
                pend.append(words)
                if len(pend) >= _FALLBACK_READ_CHUNK:
                    total += self._fused_count_read(pend)
                    pend = []
        if pend:
            total += self._fused_count_read(pend)
        return total

    @staticmethod
    def _fused_count_read(words_list) -> int:
        import jax.numpy as jnp

        from pilosa_tpu.exec import plan as planmod

        FALLBACK_STATS["count_reads"] += 1
        planmod.STATS["host_reads"] += 1
        counts = ob.popcount_rows(jnp.stack(words_list))  # dispatch-ok: per-shard path, single-device
        return int(np.asarray(counts, dtype=np.uint64).sum())

    def _sum_filter_words(self, idx: Index, c: Call, shard: int):
        if len(c.children) == 1:
            return self._bitmap_call_shard(idx, c.children[0], shard), True
        filt = c.args.get("filter")
        if isinstance(filt, Call):
            return self._bitmap_call_shard(idx, filt, shard), True
        return None, False

    _BSI_EMPTY = "empty"  # sentinel: no BSI data anywhere -> ValCount(0, 0)

    def _stacked_bsi(self, idx: Index, c: Call, f: Field, shard_list):
        """Stacked operands for a whole-field BSI aggregate (Sum/Min/Max):
        (exists, sign, planes, filter_or_None) as padded device stacks, the
        _BSI_EMPTY sentinel when there is trivially no data, or None to fall
        back to the per-shard loop."""
        if not _STACKED_ENABLED or not shard_list:
            return None
        bsiv = f.view(f.bsi_view_name())
        if bsiv is None:
            return self._BSI_EMPTY
        filter_call = None
        if len(c.children) == 1:
            filter_call = c.children[0]
        else:
            fa = c.args.get("filter")
            if isinstance(fa, Call):
                filter_call = fa
        if filter_call is not None and self._count_shifts(filter_call):
            # Shift carries need predecessor-shard augmentation (see
            # _lower_stacked); not worth plumbing here — fall back.
            return None
        # Shards without a BSI fragment contribute nothing to the aggregate
        # (the serial loop skips them), so compact the stack to present
        # shards — a sparse int field over many shards stays one dispatch.
        bsi_shards = [
            s for s in shard_list if bsiv.fragment_if_exists(s) is not None
        ]
        if not bsi_shards:
            return self._BSI_EMPTY
        from pilosa_tpu.core.devcache import DEVICE_CACHE

        low = _StackedLowering(self, idx, bsi_shards, no_sparse_guard=True)
        try:
            with lower_span("bsi"), DEVICE_CACHE.deferred_eviction():
                low._stack_guard(bsiv, mult=f.options.bit_depth + 3)
                filt = None
                if filter_call is not None:
                    root = low.lower(filter_call)
                    if isinstance(root, PZero):
                        return self._BSI_EMPTY
                    if not low.operands:
                        return None
                    sp = StackedPlan(
                        root, low.operands, low.scalars, len(bsi_shards)
                    )
                    filt = sp.rows_full()
                exists = bsiv.row_stack(BSI_EXISTS_BIT, low.shards)
                if exists is None:
                    return self._BSI_EMPTY
                sign = bsiv.row_stack(BSI_SIGN_BIT, low.shards)
                planes = bsiv.plane_stack(
                    range(BSI_OFFSET_BIT, BSI_OFFSET_BIT + f.options.bit_depth),
                    low.shards,
                )
        except BudgetExceeded:
            raise  # recoverable: _bsi_chunks halves the shard axis
        except Unsupported:
            return None
        finally:
            # extent pins here protect the staging window only (the
            # aggregate dispatches hold the assembled arrays themselves)
            low.extents.release()
        return exists, sign, planes, filt

    def _bsi_chunks(self, idx: Index, c: Call, f: Field, shard_list):
        """Stacked BSI operand sets, shard-axis-chunked under the device
        budget: a big int field costs a few dispatches, never one per
        shard. Returns a list of (exists, sign, planes, filt) tuples
        ([] = trivially empty), or None for per-shard fallback."""

        def one(chunk):
            st = self._stacked_bsi(idx, c, f, chunk)
            if st is None:
                return None
            if st == self._BSI_EMPTY:
                return []
            return [st]

        return self._chunk_by_budget(list(shard_list), one)

    def _execute_sum(self, idx: Index, c: Call, shards) -> ValCount:
        field_name = c.string_arg("field") or self._field_arg_name(c)
        f = self._field_of(idx, field_name)
        if f.options.type != FIELD_TYPE_INT:
            raise ExecError(f"field {field_name} is not an int field")
        from pilosa_tpu.exec import bsistream

        streamed = bsistream.aggregate(
            self, idx, c, f, self._shards_for(idx, shards), "sum"
        )
        if streamed is not None:
            return streamed
        chunks = self._bsi_chunks(idx, c, f, self._shards_for(idx, shards))
        if chunks is not None:
            # one jitted dispatch + one fused read per (budget-sized)
            # shard chunk — usually exactly one; exact host combine
            from pilosa_tpu.ops import bsi as obsi

            from pilosa_tpu.exec import plan as planmod

            depth = f.options.bit_depth
            count = 0
            total = 0
            for exists, sign, planes, filt in chunks:
                fused = np.asarray(
                    planmod.run_serialized(
                        lambda planes=planes, exists=exists, sign=sign,
                        filt=filt: obsi.sum_counts_stacked(
                            planes, exists, sign,
                            exists if filt is None else filt, depth
                        )
                    ),
                    dtype=np.uint64,
                )  # ONE device read: [1 + 2*depth, S]
                count += int(fused[0].sum())
                pos = fused[1 : 1 + depth].sum(axis=1)
                neg = fused[1 + depth :].sum(axis=1)
                total += sum(
                    (1 << i) * (int(pos[i]) - int(neg[i])) for i in range(depth)
                )
            return ValCount(value=total + count * f.options.base, count=count)
        bsiv = f.view(f.bsi_view_name())
        total = 0
        count = 0
        if bsiv is not None:
            for shard in self._shards_for(idx, shards):
                frag = bsiv.fragment_if_exists(shard)
                if frag is None:
                    continue
                fw, has_filter = self._sum_filter_words(idx, c, shard)
                if has_filter and fw is None:
                    continue
                s, n = frag.sum(fw, f.options.bit_depth)
                total += s
                count += n
        return ValCount(value=total + count * f.options.base, count=count)

    def _execute_min_max(self, idx: Index, c: Call, shards, is_min: bool) -> ValCount:
        field_name = c.string_arg("field") or self._field_arg_name(c)
        f = self._field_of(idx, field_name)
        if f.options.type != FIELD_TYPE_INT:
            raise ExecError(f"field {field_name} is not an int field")
        from pilosa_tpu.exec import bsistream

        streamed = bsistream.aggregate(
            self, idx, c, f, self._shards_for(idx, shards),
            "min" if is_min else "max",
        )
        if streamed is not None:
            return streamed
        chunks = self._bsi_chunks(idx, c, f, self._shards_for(idx, shards))
        if chunks is not None:
            from pilosa_tpu.ops import bsi as obsi

            from pilosa_tpu.exec import plan as planmod

            best: Optional[Tuple[int, int]] = None  # (value, count)
            for exists, sign, planes, filt in chunks:
                fused = np.asarray(
                    planmod.run_serialized(
                        lambda planes=planes, exists=exists, sign=sign,
                        filt=filt: obsi.min_max_signed(
                            planes,
                            exists,
                            sign,
                            exists if filt is None else filt,
                            f.options.bit_depth,
                            is_min,
                        )
                    ),
                    dtype=np.uint64,
                )  # ONE device read: [magnitude, negative, any, counts...]
                if not fused[2]:
                    continue
                mag = int(fused[0])
                val = -mag if fused[1] else mag
                cnt = int(fused[3:].sum())
                if best is None or (val < best[0] if is_min else val > best[0]):
                    best = (val, cnt)
                elif val == best[0]:
                    best = (val, best[1] + cnt)
            if best is None:
                return ValCount(0, 0)
            return ValCount(value=best[0] + f.options.base, count=best[1])
        bsiv = f.view(f.bsi_view_name())
        best: Optional[Tuple[int, int]] = None
        if bsiv is not None:
            for shard in self._shards_for(idx, shards):
                frag = bsiv.fragment_if_exists(shard)
                if frag is None:
                    continue
                fw, has_filter = self._sum_filter_words(idx, c, shard)
                if has_filter and fw is None:
                    continue
                val, cnt = (
                    frag.min(fw, f.options.bit_depth)
                    if is_min
                    else frag.max(fw, f.options.bit_depth)
                )
                if cnt == 0:
                    continue
                if best is None or (val < best[0] if is_min else val > best[0]):
                    best = (val, cnt)
                elif val == best[0]:
                    best = (val, best[1] + cnt)
        if best is None:
            return ValCount(0, 0)
        return ValCount(value=best[0] + f.options.base, count=best[1])

    def _execute_min_max_row(self, idx: Index, c: Call, shards, is_min: bool):
        """MinRow/MaxRow (executor.go:514-581). Filtered queries tally
        candidate rows against ONE stacked filter eval in extreme-end-first
        chunks with early stop — O(1..few) dispatches, not one per shard."""
        field_name = c.string_arg("field") or c.string_arg("_field")
        if field_name is None:
            field_name = self._field_arg_name(c)
        f = self._field_of(idx, field_name)
        v = f.view(VIEW_STANDARD)
        filter_call = c.children[0] if c.children else None
        if filter_call is not None and v is not None:
            batched = self._min_max_row_batched(
                idx, v, filter_call, self._shards_for(idx, shards), is_min
            )
            if batched is not None:
                return batched
        best_row = None
        best_count = 0
        if v is not None:
            for shard in self._shards_for(idx, shards):
                frag = v.fragment_if_exists(shard)
                if frag is None:
                    continue
                fw = (
                    self._bitmap_call_shard(idx, filter_call, shard)
                    if filter_call
                    else None
                )
                if filter_call and fw is None:
                    continue
                ids = frag.row_ids()
                if not ids:
                    continue
                if filter_call is None:
                    rid = min(ids) if is_min else max(ids)
                    if (
                        best_row is None
                        or (rid < best_row if is_min else rid > best_row)
                    ):
                        best_row, best_count = rid, 1
                    continue
                counts = frag.row_counts(ids, fw)
                for rid, cnt in zip(ids, counts):
                    if cnt == 0:
                        continue
                    if (
                        best_row is None
                        or (rid < best_row if is_min else rid > best_row)
                    ):
                        best_row, best_count = rid, int(cnt)
                    elif rid == best_row:
                        best_count += int(cnt)
        return {"id": 0 if best_row is None else best_row, "count": best_count}

    def _min_max_row_batched(
        self, idx: Index, view, filter_call: Call, shard_list, is_min: bool
    ) -> Optional[dict]:
        """Filtered MinRow/MaxRow: candidates walk from the extreme end in
        tile-bounded chunks, each tallied against the stacked filter in one
        batched pass; the first row with any filtered bits wins."""
        present = [
            (s, frag)
            for s in shard_list
            if (frag := view.fragment_if_exists(s)) is not None
        ]
        if not present:
            return {"id": 0, "count": 0}
        lowered = self._stacked_filter(idx, filter_call, present)
        if lowered is None:
            return None
        present, sp = lowered
        if not present:
            return {"id": 0, "count": 0}
        src_stack = sp.rows_full()
        from pilosa_tpu.exec import plan as planmod

        if not bool(
            np.asarray(
                planmod.run_serialized(lambda: ob.popcount(src_stack))
            )
        ):
            # filter matched nothing anywhere: no candidate can score
            return {"id": 0, "count": 0}
        cand: set = set()
        for _, frag in present:
            cand.update(frag.row_ids())
        ordered = sorted(cand, reverse=not is_min)
        chunk = self._candidate_window(len(present))
        for i in range(0, len(ordered), chunk):
            ids = ordered[i : i + chunk]
            ic = self._topn_icounts(view, ids, present, src_stack)
            for rid in ids:
                total = int(ic[rid].sum())
                if total:
                    return {"id": rid, "count": total}
        return {"id": 0, "count": 0}

    @staticmethod
    def _candidate_window(n_shards: int) -> int:
        """Candidate rows per tally round for the extreme-end MinRow/
        MaxRow walk: derived from the same quarter-budget arithmetic as
        _chunk_by_budget (each candidate tallies against a [S, W] row
        stack) instead of a hardcoded 64 — wide clusters stop paying
        extra tally dispatches when the budget would fit more
        candidates, and narrow ones stop over-chunking tiny operands."""
        from pilosa_tpu.core.devcache import DEVICE_CACHE
        from pilosa_tpu.shardwidth import WORDS_PER_ROW

        row_bytes = max(1, n_shards) * WORDS_PER_ROW * 4
        cap = max(1, DEVICE_CACHE.budget_bytes // 4)
        return int(min(4096, max(16, cap // row_bytes)))

    # ------------------------------------------------------------------
    # writes
    # ------------------------------------------------------------------

    def _execute_set(self, idx: Index, c: Call) -> bool:
        col = c.args.get("_col")
        if not isinstance(col, int):
            raise ExecError("Set() column argument required (or keys not enabled)")
        field_name = self._field_arg_name(c)
        f = self._field_of(idx, field_name)
        if f.options.type == FIELD_TYPE_INT:
            value = c.int_arg(field_name)
            if value is None:
                raise ExecError("Set() int field requires an integer value")
            changed = f.set_value(col, value)
        else:
            row_id = c.args.get(field_name)
            if f.options.type == FIELD_TYPE_BOOL:
                if not isinstance(row_id, bool):
                    raise ExecError("Set() bool field requires true/false")
                row_id = 1 if row_id else 0
            if not isinstance(row_id, int):
                raise ExecError("Set() row argument required")
            ts = c.args.get("_timestamp")
            changed = f.set_bit(
                row_id, col, timeq.parse_time(ts) if ts is not None else None
            )
        idx.track_columns(np.array([col], np.uint64))
        return changed

    def _execute_clear(self, idx: Index, c: Call) -> bool:
        col = c.args.get("_col")
        if not isinstance(col, int):
            raise ExecError("Clear() column argument required")
        field_name = self._field_arg_name(c)
        f = self._field_of(idx, field_name)
        if f.options.type == FIELD_TYPE_INT:
            return f.clear_value(col)
        row_id = c.args.get(field_name)
        if f.options.type == FIELD_TYPE_BOOL and isinstance(row_id, bool):
            row_id = 1 if row_id else 0
        if not isinstance(row_id, int):
            raise ExecError("Clear() row argument required")
        return f.clear_bit(row_id, col)

    def _execute_clear_row(self, idx: Index, c: Call, shards) -> bool:
        field_name = self._field_arg_name(c)
        f = self._field_of(idx, field_name)
        if f.options.type not in ("set", "time", "mutex", "bool"):
            raise ExecError(f"ClearRow() is not supported on {f.options.type} fields")
        row_id = c.args.get(field_name)
        if f.options.type == FIELD_TYPE_BOOL and isinstance(row_id, bool):
            row_id = 1 if row_id else 0
        if not isinstance(row_id, int):
            raise ExecError("ClearRow() row argument required")
        changed = False
        for v in list(f.views.values()):
            for shard in self._shards_for(idx, shards):
                frag = v.fragment_if_exists(shard)
                if frag is None:
                    continue
                pos = frag.row_positions(row_id)
                if len(pos):
                    frag.import_positions(
                        None,
                        np.uint64(row_id) * np.uint64(SHARD_WIDTH)
                        + pos.astype(np.uint64),
                    )
                    changed = True
        return changed

    def _execute_store(self, idx: Index, c: Call, shards) -> bool:
        """Store(Row(...), f=row): overwrite a row with the result bitmap
        (executor.go:1937 executeSetRow)."""
        if len(c.children) != 1:
            raise ExecError("Store() requires a single bitmap input")
        field_name = self._field_arg_name(c)
        f = self._field_of(idx, field_name)
        if f.options.type != "set":
            # reference executeSetRowShard (executor.go:1989) only allows set
            # fields — overwriting rows on mutex/bool would break the
            # one-row-per-column invariant, and BSI views aren't row-shaped.
            raise ExecError("Store() is only supported on set fields")
        row_id = c.args.get(field_name)
        if not isinstance(row_id, int):
            raise ExecError("Store() row argument required")
        v = f._view_create(VIEW_STANDARD)
        changed = False
        for shard in self._shards_for(idx, shards):
            words = self._bitmap_call_shard(idx, c.children[0], shard)
            new_pos = (
                ob.unpack_positions(np.asarray(words))
                if words is not None
                else np.empty(0, np.uint64)
            )
            frag = v.fragment(shard)
            old_pos = frag.row_positions(row_id).astype(np.uint64)
            to_set = np.setdiff1d(new_pos, old_pos)
            to_clear = np.setdiff1d(old_pos, new_pos)
            if len(to_set) or len(to_clear):
                base = np.uint64(row_id) * np.uint64(SHARD_WIDTH)
                frag.import_positions(
                    base + to_set if len(to_set) else None,
                    base + to_clear if len(to_clear) else None,
                )
                changed = True
        return changed

    def _execute_set_row_attrs(self, idx: Index, c: Call) -> None:
        field_name = c.args.get("_field")
        f = self._field_of(idx, field_name)
        row_id = c.args.get("_row")
        if not isinstance(row_id, int):
            raise ExecError("SetRowAttrs() row argument required")
        attrs = {
            k: v for k, v in c.args.items() if k not in ("_field", "_row")
        }
        f.row_attr_store.set_attrs(row_id, attrs)

    def _execute_set_column_attrs(self, idx: Index, c: Call) -> None:
        col = c.args.get("_col")
        if not isinstance(col, int):
            raise ExecError("SetColumnAttrs() column argument required")
        attrs = {k: v for k, v in c.args.items() if k != "_col"}
        idx.column_attr_store.set_attrs(col, attrs)

    # ------------------------------------------------------------------
    # TopN (two-pass protocol, executor.go:860-999)
    # ------------------------------------------------------------------

    def _execute_topn(self, idx: Index, c: Call, shards, opt: ExecOptions) -> List[Pair]:
        ids_arg = c.args.get("ids")
        n = c.uint_arg("n")
        if not ids_arg and not opt.remote:
            # Local one-pass: the batched tally already computes exact
            # intersection counts for every candidate across every present
            # shard, so pass 2 is a pure host-side re-select over the same
            # [R, S] matrix — ONE device read per query instead of two.
            pairs = self._topn_local_full(idx, c, shards)
            if pairs is not None:
                if n and len(pairs) > n:
                    pairs = pairs[:n]
                return pairs
        pairs = self._topn_shards(idx, c, shards)
        # ids/remote paths return untrimmed (reference executor.go:881): the
        # caller (or coordinating node) needs exact counts for every
        # candidate id to merge correctly.
        if not pairs or ids_arg or opt.remote:
            return pairs
        # Second pass: exact counts for the candidate ids.
        other = Call(c.name, dict(c.args), list(c.children))
        other.args["ids"] = sorted(p.id for p in pairs)
        trimmed = self._topn_shards(idx, other, shards)
        if n and len(trimmed) > n:
            trimmed = trimmed[:n]
        return trimmed

    def _topn_local_full(self, idx: Index, c: Call, shards) -> Optional[List[Pair]]:
        """Both TopN passes (executor.go:860-999) against ONE device tally,
        with the host side fully vectorized.

        Pass 1 selects candidates per shard from the rank caches; the
        batched tally produces exact filter-intersection counts for the
        whole candidate union across all present shards, so the pass-2
        exact recount of the merged ids is answerable from the same
        [R, S] ic matrix alone (the classic cardinality prune is implied:
        ic <= cardinality always, so ic >= threshold decides every cell)
        — no second dispatch, no second read, and no per-(row, shard)
        Python loops (the classic per-shard heap walk only runs for
        shards whose survivor pool exceeds n, where the reference's
        early-stop semantics actually bind). Returns None when the filter
        child has no stacked form or the query uses Tanimoto (both fall
        back to the classic two-pass)."""
        spec = self._topn_parse(idx, c)
        if spec.src_call is None:
            return None  # hostfast path is already zero-dispatch
        if spec.tanimoto > 0:
            return None  # rare; per-shard src counts need their own read
        shard_list = self._shards_for(idx, shards)
        vp = self._topn_present(spec, shard_list)
        if vp is None:
            return []
        v, present = vp
        lowered = self._stacked_filter(idx, spec.src_call, present)
        if lowered is None:
            return None
        present, sp = lowered
        if not present:
            return []
        TOPN_STATS["one_pass"] += 1
        src_stack = sp.rows_full()  # one plan dispatch, stays on device
        thr = np.uint64(max(spec.threshold, 1))
        # Pass 1 survivors: vectorized threshold/attr prunes over the
        # memoized rank-cache arrays.
        tops = [frag.cache_top_arrays() for _, frag in present]
        allowed_of = None
        if spec.filters is not None:
            store = spec.f.row_attr_store
            uniq = np.unique(
                np.concatenate([r for r, _ in tops])
                if tops
                else np.empty(0, np.uint64)
            )
            ok = np.fromiter(
                (
                    (val := (store.attrs(int(rid)) or {}).get(spec.attr_name))
                    is not None
                    and val in spec.filters
                    for rid in uniq
                ),
                bool,
                len(uniq),
            )

            def allowed_of(rids):
                return ok[np.searchsorted(uniq, rids)]

        surv = []
        for rids, cnts in tops:
            m = cnts >= thr
            if allowed_of is not None and m.any():
                m &= allowed_of(rids)
            surv.append((rids[m], cnts[m]))
        if not any(len(s[0]) for s in surv):
            return []
        cand = np.unique(np.concatenate([s[0] for s in surv]))
        order, fused, bundle = self._topn_icounts_raw(
            v, [int(x) for x in cand], present, src_stack
        )
        # reindex the fused tally into cand (sorted) order
        pos_of = np.empty(len(order), np.int64)
        pos_of[np.searchsorted(cand, np.asarray(order, np.uint64))] = np.arange(
            len(order)
        )
        ic_mat = fused[pos_of]  # uint64[R, S] in cand order
        # Pass 1 select per shard. Fast path: when the survivor pool fits
        # in n, the heap never fills and selection degenerates to
        # "every survivor with ic >= max(threshold, 1)" — pure numpy.
        n1 = spec.n
        merged_mask = np.zeros(len(cand), bool)
        for j, (srids, scnts) in enumerate(surv):
            if not len(srids):
                continue
            pos = np.searchsorted(cand, srids)
            ic = ic_mat[pos, j]
            if n1 == 0 or len(srids) <= n1:
                merged_mask[pos[ic >= thr]] = True
                continue
            # exact cache-order walk preserving the reference's early-stop
            # semantics (fragment.go:1570-1704) for oversized pools
            taken = 0
            low = None
            for i in range(len(srids)):
                count = int(ic[i])
                if taken < n1:
                    if count < int(thr):
                        continue
                    merged_mask[pos[i]] = True
                    taken += 1
                    low = count if low is None or count < low else low
                    continue
                if low < int(thr) or int(scnts[i]) < low:
                    break
                if count < low:
                    continue
                merged_mask[pos[i]] = True
        if not merged_mask.any():
            return []
        # Pass 2: exact totals for the merged ids — pure matrix ops. The
        # explicit-ids semantics reduce to: a (row, shard) cell contributes
        # its intersection count iff it passes the threshold (the
        # cardinality prune is implied — ic <= cardinality always).
        sel = np.flatnonzero(merged_mask)
        take = ic_mat[sel] >= thr
        totals = (ic_mat[sel] * take).sum(axis=1, dtype=np.uint64)
        pairs = [
            Pair(id=int(cand[i]), count=int(t))
            for i, t in zip(sel, totals)
            if t > 0
        ]
        pairs.sort(key=lambda p: (-p.count, p.id))
        return pairs

    def _topn_parse(self, idx: Index, c: Call) -> "_TopNSpec":
        """Validate TopN args once per pass (semantic errors raise
        identically on the batched and per-shard paths)."""
        field_name = c.args.get("_field")
        f = self._field_of(idx, field_name)
        if f.options.type == FIELD_TYPE_INT:
            raise ExecError(f"cannot compute TopN() on integer field: {field_name!r}")
        if f.options.cache_type == "none":
            raise ExecError(f'cannot compute TopN(), field has no cache: "{field_name}"')
        tanimoto = c.uint_arg("tanimotoThreshold") or 0
        if tanimoto > 100:
            raise ExecError("Tanimoto Threshold is from 1 to 100 only")
        if len(c.children) > 1:
            raise ExecError("TopN() can only have one input bitmap")
        attr_name = c.args.get("attrName")
        attr_values = c.args.get("attrValues")
        filters = None
        if attr_name and attr_values:
            filters = {fv for fv in attr_values if fv is not None}
        return _TopNSpec(
            f=f,
            n=c.uint_arg("n") or 0,
            ids=c.args.get("ids"),
            threshold=c.uint_arg("threshold") or DEFAULT_MIN_THRESHOLD,
            attr_name=attr_name,
            filters=filters,
            tanimoto=tanimoto,
            src_call=c.children[0] if c.children else None,
        )

    def _topn_pool(self, spec: "_TopNSpec", frag) -> Tuple[int, list]:
        """One shard's candidate pool in rank order (fragment.go:1703
        topBitmapPairs): explicit ids read exact counts and disable
        truncation (n=0); otherwise the rank cache is the pool, already
        sorted by count. Counts are exact O(1) host metadata either way."""
        if spec.ids:
            ids = [int(i) for i in spec.ids]
            counts = frag.cache_counts_exact(np.asarray(ids, np.uint64))
            if counts is None:
                counts = frag.row_counts_host(ids)
            pairs = [(rid, int(cnt)) for rid, cnt in zip(ids, counts) if cnt > 0]
            pairs.sort(key=lambda p: (-p[1], p[0]))
            return 0, pairs
        return spec.n, frag.cache_top()

    def _topn_survivors(self, spec: "_TopNSpec", pairs, use_tan: bool, src_count: int):
        """Host-side prunes: the cache-count window/threshold and the attr
        filter read no device data (fragment.go:1610-1668)."""
        if use_tan:
            # exclusive count window around the Tanimoto-feasible region
            min_tan = src_count * spec.tanimoto / 100.0
            max_tan = src_count * 100.0 / spec.tanimoto
        survivors: List[Tuple[int, int]] = []
        for rid, cnt in pairs:
            if cnt == 0:
                continue
            if use_tan:
                if not (min_tan < cnt < max_tan):
                    continue
            elif cnt < spec.threshold:
                continue
            if spec.filters is not None:
                attr = spec.f.row_attr_store.attrs(rid)
                if not attr:
                    continue
                val = attr.get(spec.attr_name)
                if val is None or val not in spec.filters:
                    continue
            survivors.append((rid, cnt))
        return survivors

    @staticmethod
    def _topn_select(
        spec: "_TopNSpec",
        n: int,
        survivors,
        has_src: bool,
        src_count: int,
        icounts,
    ) -> List[Tuple[int, int]]:
        """The per-shard heap selection, mirroring fragment.top exactly
        (fragment.go:1570-1704): a min-heap caps the result at n with
        threshold-based early stop; cache rank order bounds remaining
        candidates once the result set is full. The decisions depend only
        on the (pre-computed) counts, so batching the count computation
        gives identical results. Returns (count, rid) tuples."""
        import heapq
        import math

        use_tan = spec.tanimoto > 0 and has_src
        results: List[Tuple[int, int]] = []  # min-heap of (count, rid)
        for rid, cnt in survivors:
            if n == 0 or len(results) < n:
                count = icounts[rid] if has_src else cnt
                if count == 0:
                    continue
                if use_tan:
                    t = math.ceil(count * 100 / (cnt + src_count - count))
                    if t <= spec.tanimoto:
                        continue
                elif count < spec.threshold:
                    continue
                heapq.heappush(results, (count, rid))
                if n > 0 and len(results) == n and not has_src:
                    break
                continue
            # Result set full: only counts above the current minimum can
            # displace; cache rank order bounds remaining candidates.
            low = results[0][0]
            if low < spec.threshold or cnt < low:
                break
            count = icounts[rid]
            if count < low:
                continue
            heapq.heappush(results, (count, rid))
        return results

    def _topn_shards(self, idx: Index, c: Call, shards) -> List[Pair]:
        spec = self._topn_parse(idx, c)
        shard_list = self._shards_for(idx, shards)
        merged = self._topn_merged_batched(idx, spec, shard_list)
        if merged is None:
            merged = {}
            TOPN_STATS["fallback"] += 1
            for shard in shard_list:
                for count, rid in self._topn_shard(idx, spec, shard):
                    merged[rid] = merged.get(rid, 0) + count
        pairs = [Pair(id=i, count=cnt) for i, cnt in merged.items()]
        pairs.sort(key=lambda p: (-p.count, p.id))
        return pairs

    def _topn_merged_batched(
        self, idx: Index, spec: "_TopNSpec", shard_list
    ) -> Optional[Dict[int, int]]:
        """All shards' TopN tallies in one batched pass (VERDICT r2 #1: the
        last host-bound query family goes device-first).

        Candidate *selection* stays on the rank caches (exact O(1) host
        metadata — unlike the reference's approximate caches, recounting
        plain candidates is free here, fragment.go:1570 top). Only a filter
        bitmap needs device work: the child lowers to ONE stacked plan
        eval, and the survivors' intersection counts are tallied as
        popcount(planes & src) in O(candidates/tile) chunked dispatches
        with a single host read — never one dispatch per shard. Returns
        None when the child has no stacked form (per-shard fallback)."""
        if spec.src_call is None:
            TOPN_STATS["batched"] += 1
            cells = self._topn_cells(spec, shard_list)
            return {} if cells is None else self._topn_merged_hostfast(spec, cells)
        vp = self._topn_present(spec, shard_list)
        if vp is None:
            return {}
        v, present = vp
        lowered = self._stacked_filter(idx, spec.src_call, present)
        if lowered is None:
            return None
        present, sp = lowered
        TOPN_STATS["batched"] += 1
        if not present:
            return {}
        src_stack = sp.rows_full()  # one plan dispatch, stays on device
        src_counts = None
        if spec.tanimoto > 0:
            from pilosa_tpu.exec import plan as planmod

            TOPN_STATS["tally_evals"] += 1
            src_counts = np.asarray(
                planmod.run_serialized(lambda: ob.popcount_rows(src_stack)),
                dtype=np.uint64,
            )[: len(present)]
        # Per-shard pools + host-side survivor prunes.
        pools = []
        cand_union: Dict[int, None] = {}  # insertion-ordered set
        use_tan = spec.tanimoto > 0
        for j, (shard, frag) in enumerate(present):
            n, pairs = self._topn_pool(spec, frag)
            sc = int(src_counts[j]) if use_tan else 0
            survivors = self._topn_survivors(spec, pairs, use_tan, sc)
            pools.append((n, survivors, sc))
            for rid, _ in survivors:
                cand_union[rid] = None
        ic_rows: Dict[int, np.ndarray] = {}
        if cand_union:
            # canonical (sorted) candidate order: pass 2's ids are sorted,
            # so both passes chunk identically and the pass-1 plane-stack
            # cache entries are REUSED — unsorted chunks doubled the
            # host->device transfer footprint and thrashed the HBM budget
            # at bench scale (3.6 s/query vs ~0.3 s warm)
            ic_rows = self._topn_icounts(v, sorted(cand_union), present, src_stack)
        merged: Dict[int, int] = {}
        for j, (n, survivors, sc) in enumerate(pools):
            icounts = {rid: int(ic_rows[rid][j]) for rid, _ in survivors}
            for count, rid in self._topn_select(
                spec, n, survivors, True, sc, icounts
            ):
                merged[rid] = merged.get(rid, 0) + count
        return merged

    def _topn_cells(self, spec: "_TopNSpec", shard_list) -> Optional[Cells]:
        """What the no-filter-bitmap TopN selects over: per listed shard
        with a fragment, the candidate pool's (row id, cardinality) cells
        as one CSR triple (core/rowsummary.py). Read from the view's row
        summary when that holds the pools — every listed fragment's rank
        cache complete, so summary order IS pool order — else walked from
        the fragments: the rank cache in rank order, or for explicit ids
        each id's exact count. None when the view or every listed
        fragment is absent."""
        v = spec.f.view(VIEW_STANDARD)
        if v is None:
            return None
        summary = v.row_summary()
        if summary is not None:
            pos = summary.positions(shard_list)
            if summary.exact[pos].all():
                return summary.cells(pos) if len(pos) else None
        PROCESS.count("rowsummary.bypassed", 1, ())
        vp = self._topn_present(spec, shard_list)
        if vp is None:
            return None
        frags = [frag for _, frag in vp[1]]
        if not spec.ids:
            return cells_of([frag.cache_top_arrays() for frag in frags])
        # explicit ids: exact cardinalities from the rank cache when it is
        # provably complete (vectorized lookup), else the authoritative
        # row_counts_host walk
        ids_arr = np.unique(np.asarray([int(i) for i in spec.ids], np.uint64))
        ids = ids_arr.tolist()
        parts = []
        for frag in frags:
            c = frag.cache_counts_exact(ids_arr)
            parts.append((ids_arr, frag.row_counts_host(ids) if c is None else c))
        return cells_of(parts)

    def _topn_merged_hostfast(self, spec: "_TopNSpec", cells: Cells) -> Dict[int, int]:
        """The no-filter-bitmap merge: counts are exact O(1) host metadata,
        so both passes reduce to segment operations over the shards'
        cells (_topn_cells) — zero device dispatches, no per-fragment
        call. Semantics identical to _topn_pool/_topn_survivors/
        _topn_select with has_src=False (the differential tests force the
        general path and compare)."""
        rids, counts, offsets = cells
        merged: Dict[int, int] = {}
        allowed = None
        if spec.filters is not None:
            store = spec.f.row_attr_store

            def allowed(rid: int) -> bool:
                attr = store.attrs(rid)
                val = attr.get(spec.attr_name) if attr else None
                return val is not None and val in spec.filters

        thr = np.uint64(max(spec.threshold, 1))
        if spec.ids:
            # pass 2 / explicit ids: no truncation -> the per-shard select
            # reduces to "sum counts >= threshold per shard" (exact)
            ids = [int(i) for i in spec.ids]
            if allowed is not None:
                ids = [rid for rid in ids if allowed(rid)]
            if not ids:
                return merged
            uniq = np.unique(np.asarray(ids, np.uint64))
            pos = np.minimum(np.searchsorted(uniq, rids), len(uniq) - 1)
            keep = (uniq[pos] == rids) & (counts >= thr)
            # float64 weights are exact below 2^53; per-row totals are
            # bounded by n_shards * SHARD_WIDTH, far under that
            totals = np.bincount(
                pos[keep], weights=counts[keep].astype(np.float64),
                minlength=len(uniq),
            )
            total_of = dict(zip(uniq.tolist(), totals.tolist()))
            for rid in ids:
                cnt = int(total_of[rid])
                if cnt:
                    merged[rid] = merged.get(rid, 0) + cnt
            return merged
        # pass 1: per-shard top-n of the rank cache: the threshold cut and
        # the attr filter are masks over the cells, the n-bound keeps each
        # shard's first n survivors in rank order — same contract as the
        # select heap with no src. The merge is one bincount over the
        # selections.
        keep = counts >= thr
        if allowed is not None and keep.any():
            uniq = np.unique(rids[keep])
            ok = np.fromiter((allowed(int(r)) for r in uniq), bool, len(uniq))
            kept = np.flatnonzero(keep)
            keep[kept[~ok[np.searchsorted(uniq, rids[kept])]]] = False
        if spec.n:
            keep = head_per_segment(keep, offsets, spec.n)
        if keep.any():
            uniq, inv = np.unique(rids[keep], return_inverse=True)
            totals = np.bincount(inv, weights=counts[keep].astype(np.float64))
            for rid, t in zip(uniq.tolist(), totals.tolist()):
                merged[rid] = int(t)
        return merged

    def _topn_present(self, spec: "_TopNSpec", shard_list):
        """Shared TopN preamble: (standard view, present fragments), or
        None when the view or every listed fragment is absent."""
        v = spec.f.view(VIEW_STANDARD)
        if v is None:
            return None
        present = [
            (s, frag)
            for s in shard_list
            if (frag := v.fragment_if_exists(s)) is not None
        ]
        if not present:
            return None
        # cross-fragment merge barrier: rank caches and tally bundles are
        # about to read every present fragment — merge the whole staged
        # burst as one batched pass, not one host pass per fragment
        v.sync_pending(frags=[frag for _, frag in present])
        return (v, present)

    def _stacked_filter(self, idx: Index, filter_call: Call, present):
        """Lower a filter bitmap over the present (shard, fragment) pairs
        for a batched tally. Returns (present, plan) with `present`
        restricted to the plan's out_shards when compaction dropped shards
        — those have no filter bits anywhere, so they contribute nothing
        (the per-shard paths skip None filter words the same way). None =
        no stacked form (per-shard fallback)."""
        pshards = [s for s, _ in present]
        sp = self._lower_stacked(idx, filter_call, pshards)
        if sp is None:
            return None
        if sp.out_shards != pshards:
            outs = set(sp.out_shards)
            present = [(s, frag) for s, frag in present if s in outs]
        return present, sp

    def _topn_icounts(
        self, view, cand: List[int], present, src_stack
    ) -> Dict[int, np.ndarray]:
        order, fused, _ = self._topn_icounts_raw(view, cand, present, src_stack)
        return {rid: fused[k] for k, rid in enumerate(order)}

    def _topn_icounts_raw(
        self, view, cand: List[int], present, src_stack
    ) -> Tuple[List[int], np.ndarray, "_TallyBundle"]:
        """Intersection counts for every candidate row across all present
        shards with ONE blocking device read (per-chunk reads would each
        be a synchronisation): (row order, uint64[R, S] matrix). Candidates
        split by host representation: rows sparse in every present shard
        contribute only their live words (device gather + sorted-segment
        cumsum — HBM traffic ~ bytes of live words, not full zero-padded
        planes, and no TPU scatter); rows dense anywhere go through
        chunked [R_c, S, W] plane stacks. All partial counts concatenate
        on device into a single fused [R, S] read."""
        from pilosa_tpu.exec import groupby as gb

        import jax.numpy as jnp

        pshards = tuple(s for s, _ in present)
        n_present = len(present)
        s_pad, w = src_stack.shape
        bundle = self._topn_tally_bundle(view, cand, present, w)
        dense_rows, sparse_rows, dev = (
            bundle.dense_rows,
            bundle.sparse_rows,
            bundle.dev,
        )
        from pilosa_tpu.exec import plan as planmod

        parts = []  # device uint32 [*, n_present] blocks (materialized)
        order: List[int] = []  # row ids aligned with the fused row axis
        if dense_rows:
            r_c = gb._gmax(s_pad, w)
            for i in range(0, len(dense_rows), r_c):
                ids = dense_rows[i : i + r_c]
                pad_ids = [int(x) for x in gb._pad_pow2(np.asarray(ids))]
                # staging OUTSIDE the dispatch mutex (transfers overlap
                # the in-flight program; they don't rendezvous)
                planes = view.plane_stack(pad_ids, pshards)
                src = src_stack
                if planes.shape[1] != s_pad:
                    # stacked src may carry extra Shift-predecessor shards
                    src = src_stack[: planes.shape[1]]
                TOPN_STATS["tally_evals"] += 1
                # tally programs consume mesh-sharded stacks: serialized
                # like every other compiled dispatch (plan.run_serialized)
                parts.append(
                    planmod.run_serialized(
                        lambda src=src, planes=planes, n=len(ids):
                        gb.cross_tally(src[None], planes)[0][:n, :n_present]
                    )
                )
                order.extend(ids)
        if sparse_rows:
            if dev is None:
                parts.append(
                    jnp.zeros((len(sparse_rows), n_present), jnp.uint32)
                )
            else:
                idx, mask, starts, ends, r_pad, s_pow2 = dev
                TOPN_STATS["tally_evals"] += 1
                parts.append(
                    planmod.run_serialized(
                        lambda: ob.gather_tally_sorted(
                            src_stack, idx, mask, starts, ends
                        ).reshape(r_pad, s_pow2)[: len(sparse_rows), :n_present]
                    )
                )
            order.extend(sparse_rows)
        if not order:
            return [], np.empty((0, n_present), np.uint64), bundle
        fused = np.asarray(
            parts[0]
            if len(parts) == 1
            else planmod.run_serialized(
                lambda: jnp.concatenate(parts, axis=0)
            ),
            dtype=np.uint64,
        )
        return order, fused, bundle

    def _topn_tally_bundle(self, view, cand: List[int], present, w: int) -> "_TallyBundle":
        """Prepared inputs for the candidate tally (see _TallyBundle).

        Sparse rows' live bits are folded to per-(row, shard) word entries
        in ONE vectorized host pass (sort + reduceat over every bit of
        every sparse candidate — no per-(row, shard) numpy calls), then
        cached in DEVICE_CACHE keyed by fragment versions, so warm queries
        skip the host build entirely. No cardinality data is stored: the
        pass-2 cardinality prune is implied by ic <= cardinality, so the
        ic matrix alone decides every cell (Tanimoto, which genuinely
        needs per-shard cardinalities, takes the classic two-pass)."""
        from pilosa_tpu.core.devcache import DEVICE_CACHE

        key = view._stack_key(
            "topn_sparse", tuple(cand), tuple(s for s, _ in present)
        )
        return DEVICE_CACHE.get_or_build(
            key,
            lambda: self._topn_tally_build(cand, present, w),
            index=view.index,
        )

    def _topn_tally_build(self, cand: List[int], present, w: int) -> "_TallyBundle":
        import jax

        r_all = len(cand)
        n_present = len(present)
        cats, lens = [], []
        for _, frag in present:
            c_, l_ = frag.rows_sparse_concat(cand)
            cats.append(c_)
            lens.append(l_)
        lens_mat = np.stack(lens)  # [S, R]; -1 marks dense-rep
        dense_mask = (lens_mat < 0).any(axis=0)
        n_bits = int(np.clip(lens_mat, 0, None).sum())
        if n_bits >= 1 << 27:
            # uint32 cumsum headroom (gather_tally_sorted): route everything
            # through the plane path instead
            dense_mask = np.ones(r_all, bool)
        dense_rows = [rid for i, rid in enumerate(cand) if dense_mask[i]]
        sparse_rows = [rid for i, rid in enumerate(cand) if not dense_mask[i]]
        dev = None
        if sparse_rows:
            # pow2-pad BOTH segment axes (rows and shards): every distinct
            # input shape forces a fresh XLA compile of gather_tally_sorted,
            # so shapes must come from a log-bounded family
            s_pow2 = 1 << max(n_present - 1, 0).bit_length()
            k_of = np.full(r_all, -1, np.int64)
            k_of[~dense_mask] = np.arange(len(sparse_rows))
            wkey_parts, bit_parts = [], []
            for j in range(n_present):
                l_ = np.clip(lens_mat[j], 0, None)
                if not l_.sum():
                    continue
                rows_per_el = np.repeat(np.arange(r_all), l_)
                keep = ~dense_mask[rows_per_el]
                pos = cats[j][keep].astype(np.int64)
                seg = k_of[rows_per_el[keep]] * s_pow2 + j
                wkey_parts.append(seg * w + (pos >> 5))
                bit_parts.append(
                    np.uint32(1) << (pos & np.int64(31)).astype(np.uint32)
                )
            if wkey_parts:
                wkeys = np.concatenate(wkey_parts)
                bits = np.concatenate(bit_parts)
                o = np.argsort(wkeys, kind="stable")
                sk, sb = wkeys[o], bits[o]
                new_grp = np.empty(len(sk), bool)
                new_grp[0] = True
                np.not_equal(sk[1:], sk[:-1], out=new_grp[1:])
                gstart = np.flatnonzero(new_grp)
                masks = np.bitwise_or.reduceat(sb, gstart)
                uk = sk[gstart]
                seg_of = uk // w
                idx = ((seg_of % s_pow2) * w + uk % w).astype(np.int32)
                # pad the entry axis to pow2 too; padding lands after every
                # segment end, so sums are unaffected
                k_pad = 1 << max(len(idx) - 1, 0).bit_length()
                if k_pad != len(idx):
                    padn = k_pad - len(idx)
                    idx = np.concatenate([idx, np.zeros(padn, np.int32)])
                    masks = np.concatenate([masks, np.zeros(padn, np.uint32)])
                r_pad = 1 << max(len(sparse_rows) - 1, 0).bit_length()
                segs = np.arange(r_pad * s_pow2)
                starts = np.searchsorted(seg_of, segs, "left").astype(np.int32)
                ends = np.searchsorted(seg_of, segs, "right").astype(np.int32)
                dev = (
                    jax.device_put(idx),
                    jax.device_put(masks),
                    jax.device_put(starts),
                    jax.device_put(ends),
                    r_pad,
                    s_pow2,
                )
        return _TallyBundle(dense_rows, sparse_rows, dev)

    def _topn_shard(self, idx: Index, spec: "_TopNSpec", shard: int) -> List[Tuple[int, int]]:
        """One shard's TopN candidates (the per-shard fallback when the
        filter child has no stacked form). Same pool/prune/select pipeline
        as the batched path; intersection counts for surviving candidates
        come from one batched per-shard dispatch."""
        src = None
        if spec.src_call is not None:
            src = self._bitmap_call_shard(idx, spec.src_call, shard)
            if src is None:
                return []
        v = spec.f.view(VIEW_STANDARD)
        if v is None:
            return []
        frag = v.fragment_if_exists(shard)
        if frag is None:
            return []
        n, pairs = self._topn_pool(spec, frag)
        if not pairs:
            return []
        has_src = src is not None
        src_count = int(ob.popcount(src)) if has_src else 0  # dispatch-ok: per-shard path, single-device
        use_tan = spec.tanimoto > 0 and has_src
        survivors = self._topn_survivors(spec, pairs, use_tan, src_count)
        icounts: Optional[Dict[int, int]] = None
        if has_src and survivors:
            cand = [rid for rid, _ in survivors]
            icounts = {
                rid: int(cnt) for rid, cnt in zip(cand, frag.row_counts(cand, src))
            }
        return self._topn_select(spec, n, survivors, has_src, src_count, icounts)

    # ------------------------------------------------------------------
    # Rows / GroupBy (executor.go:1068-1273)
    # ------------------------------------------------------------------

    def _execute_rows(self, idx: Index, c: Call, shards) -> List[int]:
        field_name = c.string_arg("field") or c.args.get("_field")
        if not field_name:
            raise ExecError("Rows() field required")
        col = c.uint_arg("column")
        if col is not None:
            shards = [col // SHARD_WIDTH]
        limit = c.uint_arg("limit")
        out = self._rows_summary(idx, field_name, c, shards)
        if out is None:
            PROCESS.count("rowsummary.bypassed", 1, ())
            merged: set = set()
            for shard in self._shards_for(idx, shards):
                merged.update(self._rows_shard(idx, field_name, c, shard))
            out = sorted(merged)
        prev = c.uint_arg("previous")
        if prev is not None:
            out = [r for r in out if r > prev]
        if limit is not None:
            out = out[:limit]
        return out

    def _rows_summary(
        self, idx: Index, field_name: str, c: Call, shards
    ) -> Optional[List[int]]:
        """Rows() of a field's standard view from the view's row summary
        (core/rowsummary.py): the ids with a non-zero count in any listed
        shard, one vector pass instead of a call per (row, fragment).
        None when the call reads what the table does not hold — one
        column's membership, time-quantum views, cold shards — and the
        per-fragment walk answers."""
        if c.uint_arg("column") is not None:
            return None
        f = self._field_of(idx, field_name)
        if f.options.type == FIELD_TYPE_TIME and (
            c.args.get("from") is not None
            or c.args.get("to") is not None
            or f.options.no_standard_view
        ):
            return None
        v = f.view(VIEW_STANDARD)
        if v is None:
            return []
        summary = v.row_summary()
        if summary is None:
            return None
        pos = summary.positions(self._shards_for(idx, shards))
        return np.unique(summary.cells(pos)[0]).tolist()

    def _rows_shard(self, idx: Index, field_name: str, c: Call, shard: int) -> List[int]:
        f = self._field_of(idx, field_name)
        views = [VIEW_STANDARD]
        from_arg = c.args.get("from")
        to_arg = c.args.get("to")
        if f.options.type == FIELD_TYPE_TIME and (
            from_arg is not None or to_arg is not None or f.options.no_standard_view
        ):
            if not f.options.time_quantum:
                return []
            lo, hi = self._field_time_bounds(f)
            if lo is None:
                return []
            from_t = timeq.parse_time(from_arg) if from_arg is not None else lo
            to_t = timeq.parse_time(to_arg) if to_arg is not None else hi
            views = timeq.views_by_time_range(VIEW_STANDARD, from_t, to_t, f.options.time_quantum)
        col = c.uint_arg("column")
        if col is not None and col // SHARD_WIDTH != shard:
            return []
        out: set = set()
        for vname in views:
            v = f.view(vname)
            if v is None:
                continue
            frag = v.fragment_if_exists(shard)
            if frag is None:
                continue
            ids = frag.row_ids()
            if col is not None:
                ids = [r for r in ids if frag.contains(r, col % SHARD_WIDTH)]
            else:
                ids = [r for r in ids if frag.row_count(r) > 0]
            out.update(ids)
        return sorted(out)

    def _execute_group_by(self, idx: Index, c: Call, shards) -> List[GroupCount]:
        if not c.children:
            raise ExecError("need at least one child call")
        for child in c.children:
            if child.name != "Rows":
                raise ExecError(
                    f"'{child.name}' is not a valid child query for GroupBy, must be 'Rows'"
                )
        unread = sorted(set(c.args) - _GROUPBY_ARGS)
        if unread:
            raise ExecError(
                f"GroupBy does not take the argument '{unread[0]}' (it reads "
                "filter, limit, offset, previous and aggregate)"
            )
        limit = c.uint_arg("limit")
        filter_call = c.args.get("filter")
        if filter_call is not None and not isinstance(filter_call, Call):
            raise ExecError("GroupBy filter must be a query")
        value_field = (
            self._group_by_aggregate_field(idx, c)
            if "aggregate" in c.args else None
        )

        # Pagination cursor: per-child Rows(previous=) args plus the
        # GroupBy-level previous=[...] list form; both resume the sorted
        # cross-product strictly after the previous group (reference
        # groupByIterator seek, executor.go:3121-3160 — per-child Seek with
        # wrap/ignorePrev cascades is equivalent to a lexicographic ">"
        # against the tuple (prev_i or first-row_i)).
        prevs: List[Optional[int]] = [ch.uint_arg("previous") for ch in c.children]
        gprev = c.args.get("previous")
        if gprev is not None:
            # shape errors surface in translate_call (translation.py) before
            # execution; this guard only covers direct programmatic calls
            if not isinstance(gprev, list) or len(gprev) != len(c.children):
                raise ExecError(
                    "GroupBy previous must be a list with one entry per child"
                )
            for i, pv in enumerate(gprev):
                if prevs[i] is None:
                    prevs[i] = int(pv)
        has_prev = any(p is not None for p in prevs)

        # Pre-fetch child row id lists (cluster-wide semantics). Without a
        # child limit/column, the previous arg must NOT prune the row list:
        # a non-last child's previous row still heads later groups (e.g.
        # (prev, prev+1, ...)) — the cursor is applied to whole group tuples
        # below. WITH limit or column the reference prefetches via
        # executeRows, which applies previous before limit (executor.go:
        # 1101-1115 + 1403), so the pruned list is the group row universe.
        child_fields = []
        child_rows: List[List[int]] = []
        for child in c.children:
            fname = child.string_arg("field") or child.args.get("_field")
            child_fields.append(fname)
            saved_prev = None
            if "limit" not in child.args and "column" not in child.args:
                saved_prev = child.args.pop("previous", None)
            try:
                child_rows.append(self._execute_rows(idx, child, shards))
            finally:
                if saved_prev is not None:
                    child.args["previous"] = saved_prev
            if not child_rows[-1]:
                return []

        anchor: Optional[Tuple[int, ...]] = None
        if has_prev:
            # The reference seek position: children without a previous value
            # anchor at their first row, the last child seeks one past its
            # previous value, and the landing group itself is included —
            # i.e. the result keeps group tuples >= the anchor tuple.
            last = len(c.children) - 1
            anchor = tuple(
                (prevs[i] + (1 if i == last else 0))
                if prevs[i] is not None
                else child_rows[i][0]
                for i in range(len(c.children))
            )
            # Any tuple with first component < anchor[0] compares below the
            # anchor regardless of deeper values, so the first child's rows
            # can be pruned before tallying — deep pages skip the bulk of
            # the cross-product instead of tallying and discarding it.
            child_rows[0] = [r for r in child_rows[0] if r >= anchor[0]]
            if not child_rows[0]:
                return []

        shard_list = self._shards_for(idx, shards)
        merged = self._group_by_stacked(
            idx, child_fields, child_rows, filter_call, shard_list,
            value_field,
        )
        if merged is None:
            merged = {}
            for shard in shard_list:
                fw = (
                    self._bitmap_call_shard(idx, filter_call, shard)
                    if filter_call is not None
                    else None
                )
                if filter_call is not None and fw is None:
                    continue
                self._group_by_shard(
                    idx, child_fields, child_rows, fw, shard, merged,
                    value_field,
                )
        if anchor is not None:
            merged = {k: v for k, v in merged.items() if k >= anchor}
        if value_field is not None:
            # (count, stored sum, values) -> count and the sum of the
            # field's values: the stored ones are relative to its base
            base = value_field.options.base
            merged = {
                k: (cnt, stored + n * base)
                for k, (cnt, stored, n) in merged.items()
            }
        else:
            merged = {k: (cnt, None) for k, cnt in merged.items()}
        out = [
            GroupCount(
                group=[
                    FieldRow(field=fn, row_id=rid)
                    for fn, rid in zip(child_fields, key)
                ],
                count=cnt,
                sum=total,
            )
            for key, (cnt, total) in merged.items()
            if cnt > 0
        ]
        out.sort(key=lambda g: g.compare_key())
        offset = c.uint_arg("offset")
        if offset:
            out = out[offset:]
        if limit is not None:
            out = out[:limit]
        return out

    def _group_by_aggregate_field(self, idx: Index, c: Call) -> Field:
        """The int field of a GroupBy's `aggregate=Sum(field=<f>)`; any
        other shape of the argument raises, naming it."""
        agg = c.args["aggregate"]
        if not isinstance(agg, Call):
            raise ExecError(
                f"GroupBy aggregate must be a call, Sum(field=<int field>), "
                f"not {agg!r}"
            )
        if agg.name != "Sum":
            raise ExecError(
                f"GroupBy aggregate '{agg.name}' is not supported: the "
                "aggregate is Sum(field=<int field>)"
            )
        field_name = agg.string_arg("field")
        extra = sorted(set(agg.args) - {"field"})
        if field_name is None or extra or agg.children:
            what = (
                f"the argument '{extra[0]}'" if extra
                else "a child query" if agg.children else "no field="
            )
            raise ExecError(
                f"GroupBy aggregate Sum takes field=<int field> alone, "
                f"found {what}"
            )
        f = self._field_of(idx, field_name)
        if f.options.type != FIELD_TYPE_INT:
            raise ExecError(
                f"GroupBy aggregate Sum: field {field_name} is not an int field"
            )
        return f

    def _group_by_stacked(
        self, idx, child_fields, child_rows, filter_call, shard_list,
        value_field: Optional[Field] = None,
    ) -> Optional[Dict[Tuple[int, ...], Any]]:
        """Tally the whole GroupBy cross-product in O(depth) batched device
        dispatches over stacked [R, S, W] operands, each handed over as
        the tuple of its resident extents (exec/groupby.py),
        replacing the per-(prefix, depth) dispatch + host sync of the
        recursive walk. With `value_field` (aggregate=Sum) its BSI planes
        are staged beside the dimensions, one stack through the same
        residency layer, and a group's value is (count, stored sum,
        values) instead of its count. Returns None to fall back to the
        per-shard path (stacked lowering unsupported for this
        shape/budget)."""
        if not _STACKED_ENABLED or not shard_list:
            return None
        if filter_call is not None and self._count_shifts(filter_call):
            return None
        child_views = []
        for fname in child_fields:
            f = self._field_of(idx, fname)
            v = f.view(VIEW_STANDARD)
            if v is None:
                return {}
            child_views.append(v)
        # A shard contributes a group only when EVERY child has a fragment
        # there (the per-shard walk returns early otherwise) — compact the
        # stacks to that intersection so sparse fields stay cheap.
        gb_shards = shard_list
        for v in child_views:
            summary = v.row_summary()
            if summary is not None:
                gb_shards = summary.present(gb_shards)
            else:
                gb_shards = [
                    s for s in gb_shards if v.fragment_if_exists(s) is not None
                ]
        if not gb_shards:
            return {}
        from pilosa_tpu.core.devcache import DEVICE_CACHE

        low = _StackedLowering(self, idx, gb_shards, no_sparse_guard=True)
        planes_list = []
        try:
            with lower_span("groupby"), DEVICE_CACHE.deferred_eviction():
                filt = None
                if filter_call is not None:
                    root = low.lower(filter_call)
                    if isinstance(root, PZero) or not low.operands:
                        return {}  # filter matches nothing anywhere
                    filt = StackedPlan(
                        root, low.operands, low.scalars, len(gb_shards)
                    ).rows_full()
                for v, rows in zip(child_views, child_rows):
                    low._stack_guard(v, mult=max(len(rows), 1))
                    # the resident extents as they are: every child is
                    # staged over low.shards at one extent size, so the
                    # parts line up span for span and the tally reads
                    # them in place (exec/groupby.py)
                    p = v.plane_stack(rows, low.shards, parts=True)
                    if p is None:
                        return {}
                    planes_list.append(p)
                value = None
                if value_field is not None:
                    # exists, sign and the magnitude planes as ONE stack
                    # over the same shards: a shard without a BSI fragment
                    # is a zero slab (no column there holds a value)
                    depth = value_field.options.bit_depth
                    bsiv = value_field.view(value_field.bsi_view_name())
                    if bsiv is not None:
                        low._stack_guard(bsiv, mult=depth + 2)
                        value = bsiv.plane_stack(
                            range(BSI_OFFSET_BIT + depth), low.shards,
                            parts=True,
                        )
        except Unsupported:
            return None
        finally:
            low.extents.release()  # staging-window pins (see _stacked_bsi)
        if value_field is not None and value is None:
            # no column holds a value anywhere: the groups, with nothing summed
            counted = self._group_by_dispatch(planes_list, child_rows, filt)
            return {k: (cnt, 0, 0) for k, cnt in counted.items()}
        return self._group_by_dispatch(
            planes_list, child_rows, filt, value, value_field
        )

    @staticmethod
    def _group_by_dispatch(
        planes_list, child_rows, filt, value=None, value_field=None
    ):
        """The whole tally pipeline — the cross tally, or with `value` (the
        aggregate's plane stack) the group tally — as ONE exec.dispatch."""
        from pilosa_tpu.exec import groupby as qgb
        from pilosa_tpu.exec import plan as planmod

        # the whole cross-tally pipeline (multiple dispatches + reads over
        # mesh-sharded plane stacks) runs as one serialized occupancy of
        # the device — concurrent GroupBy legs from other in-process nodes
        # must not interleave collective-bearing programs (plan.run_serialized
        # rationale); operands above were staged before entry. run_counted
        # books it as one exec.dispatch span (its reads happen inside), so
        # a profile shows the answer came from the device
        def tally():
            launched = _group_by_launches()
            if value is None:
                out = qgb.group_by_device(planes_list, child_rows, filt)
                info = {"levels": len(planes_list), "live_groups": len(out),
                        "planes": 0, "fold_ms": 0.0}
            else:
                o, info = value_field.options, {}
                out = qgb.group_by_aggregate(
                    planes_list, child_rows, filt, value, o.bit_depth,
                    signed=o.min < o.base, info=info,
                )
            _tag_group_by(_group_by_launches() - launched, info)
            return out

        return planmod.run_counted(
            tally, read=False, family="groupby",
            program=(
                qgb.tally_program(planes_list, filt) if value is None
                else qgb.group_program(planes_list, value, filt)
            ),
            arrays=planes_list,
        )

    def _group_by_shard(  # dispatch-ok: per-shard path, single-device
        self, idx, child_fields, child_rows, filter_words, shard, merged,
        value_field: Optional[Field] = None,
    ) -> None:
        """Nested cross-product with zero-count pruning (the reference's
        groupByIterator, executor.go:3063). With `value_field` a group's
        entry is (count, stored sum, values), the last two from the
        shard's BSI fragment over the group's words."""
        value_frag = None
        if value_field is not None:
            bsiv = value_field.view(value_field.bsi_view_name())
            if bsiv is not None:
                value_frag = bsiv.fragment_if_exists(shard)
        frags = []
        for fname in child_fields:
            f = self._field_of(idx, fname)
            v = f.view(VIEW_STANDARD)
            frag = v.fragment_if_exists(shard) if v is not None else None
            if frag is None:
                return
            frags.append(frag)

        def recurse(depth: int, acc_words, prefix: Tuple[int, ...]):
            frag = frags[depth]
            ids = [r for r in child_rows[depth] if frag.has_row(r)]
            if not ids:
                return
            counts = frag.row_counts(ids, acc_words)
            for rid, cnt in zip(ids, counts):
                if cnt == 0:
                    continue
                key = prefix + (rid,)
                if depth == len(frags) - 1 and value_field is not None:
                    stored = n = 0
                    if value_frag is not None:
                        words = frag.row_device(rid)
                        if acc_words is not None:
                            words = ob.b_and(acc_words, words)
                        stored, n = value_frag.sum(
                            words, value_field.options.bit_depth
                        )
                    was = merged.get(key, (0, 0, 0))
                    merged[key] = (
                        was[0] + int(cnt), was[1] + stored, was[2] + n
                    )
                elif depth == len(frags) - 1:
                    merged[key] = merged.get(key, 0) + int(cnt)
                else:
                    words = frag.row_device(rid)
                    nxt = words if acc_words is None else ob.b_and(acc_words, words)
                    recurse(depth + 1, nxt, key)

        recurse(0, filter_words, ())

    # ------------------------------------------------------------------
    # Options (executor.go:360)
    # ------------------------------------------------------------------

    def _execute_options(self, idx: Index, c: Call, shards, opt: ExecOptions):
        if len(c.children) != 1:
            raise ExecError("Options() requires a single child query")
        new_opt = ExecOptions(
            remote=opt.remote,
            exclude_row_attrs=bool(c.args.get("excludeRowAttrs", opt.exclude_row_attrs)),
            exclude_columns=bool(c.args.get("excludeColumns", opt.exclude_columns)),
            column_attrs=bool(c.args.get("columnAttrs", opt.column_attrs)),
            max_writes=opt.max_writes,
        )
        # columnAttrs is read at response level, so it must propagate to the
        # caller's options (reference mutates the shared opt, executor.go:368)
        opt.column_attrs = new_opt.column_attrs
        s = c.args.get("shards")
        if s is not None:
            if not isinstance(s, list):
                raise ExecError("Options() shards must be a list")
            shards = [int(x) for x in s]
        return self._execute_call(idx, c.children[0], shards, new_opt)

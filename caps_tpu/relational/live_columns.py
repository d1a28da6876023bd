"""Live-column analysis: which header entries the plan above an operator
still reads, so a join or a filter gathers only those (projection
push-down; the reference gets it from Catalyst's column pruning, the
``Table`` SPI has no optimizer underneath).

One top-down pass over the relational tree, run once per plan in
``session._plan_ir``.  Every operator gets ``required``: ``None`` (its
whole output may be read — today's behaviour, and what every operator the
pass does not model hands to its children) or a :class:`Required` set of
two kinds of item, because headers exist only at run time:

* a concrete header expression (``Var(r)`` = its id column,
  ``Property(Var(a), "name")``, ``StartNode(Var(r))``, ...);
* *everything owned by* var ``v``.

``JoinOp``/``FilterOp`` turn the set into column names against their
run-time header (:meth:`Required.narrow`) and pass them as ``keep`` to
``Table.join``/``Table.filter``.

A column dropped too early does not fail: ``ops.resolve_expr`` rewrites a
``Property`` the header lacks to null and a ``HasLabel`` to false, and
decides both by ``header.entity_vars``.  Hence: whenever anything owned
by ``v`` is required, ``Var(v)`` is required with it; expressions are
read as the operators resolve them (``HasType(r, T)`` reads ``Type(r)``);
and when in doubt an expression reads everything its vars own.
"""
from __future__ import annotations

from typing import Dict, FrozenSet, Iterable, List, Optional, Set, Tuple

from caps_tpu.ir import exprs as E
from caps_tpu.relational import ops as R
from caps_tpu.relational.header import RecordHeader

#: header-entry shapes other than ``Var``: class -> the field holding the
#: owning var
_ENTRY_OWNER = {
    E.Property: "entity", E.HasLabel: "node", E.StartNode: "rel",
    E.EndNode: "rel", E.Type: "rel", E.PathSeg: "path", E.PathNode: "path",
}

#: a bare entity ``Var`` directly under one of these reads its id column
#: only (both evaluators resolve it through ``header.column(Var(v))``)
_ID_ONLY_PARENTS = (E.Id, E.Count, E.IsNull, E.IsNotNull)


def _owner(e: E.Expr) -> Optional[str]:
    """The var owning a header entry of a canonical shape, else None."""
    if type(e) is E.Var:
        return e.name
    field = _ENTRY_OWNER.get(type(e))
    if field is not None:
        v = getattr(e, field)
        if type(v) is E.Var:
            return v.name
    return None


class Required:
    """What the ancestors of an operator read of its output."""

    __slots__ = ("exprs", "owners", "_partial")

    def __init__(self, exprs: Iterable[E.Expr] = (),
                 owners: Iterable[str] = ()):
        #: vars of which everything owned is read
        self.owners: FrozenSet[str] = frozenset(owners)
        closed: Set[E.Expr] = set()
        partial: Set[str] = set()
        for e in exprs:
            names = [v.name for v in E.vars_in(e)]
            if names and all(n in self.owners for n in names):
                continue  # covered by an owner item
            closed.add(e)
            for n in names:
                # resolve_expr decides by header.entity_vars: the var
                # itself travels with anything it owns
                closed.add(E.Var(n))
                partial.add(n)
        #: concrete header expressions read
        self.exprs: FrozenSet[E.Expr] = frozenset(closed)
        self._partial = frozenset(partial)

    def union(self, other: Optional["Required"]) -> Optional["Required"]:
        if other is None:
            return None
        if other is self or (other.exprs <= self.exprs
                             and other.owners <= self.owners):
            return self
        return Required(self.exprs | other.exprs, self.owners | other.owners)

    def reading(self, exprs: Iterable[E.Expr],
                ids_only: Iterable[E.Expr] = ()) -> "Required":
        """This set plus what ``exprs`` read; a bare var among
        ``ids_only`` (a join key) reads its id column only."""
        es: Set[E.Expr] = set(self.exprs)
        os_: Set[str] = set(self.owners)
        for e in exprs:
            expr_reads(e, es, os_)
        for e in ids_only:
            expr_reads(e, es, os_, bare_is_id=True)
        return Required(es, os_)

    def narrow(self, header: RecordHeader) -> RecordHeader:
        """``header`` cut to the required entries (the same object when
        every column survives)."""
        owners, partial, exprs = self.owners, self._partial, self.exprs
        kept = []
        for entry in header._entries:
            e = entry[0]
            o = _owner(e)
            if o is None:
                names = [v.name for v in E.vars_in(e)]
                if not names or any(n in owners or n in partial
                                    for n in names):
                    kept.append(entry)  # unusual entry: when in doubt
            elif o in owners or (o in partial and e in exprs):
                kept.append(entry)
        if len(kept) == len(header._entries):
            return header
        return RecordHeader(kept)

    def describe(self, names: Optional[Set[str]] = None) -> str:
        """``keeps=[...]`` for EXPLAIN; ``names`` (the vars bound below
        the operator, where known) leaves out what it cannot have."""
        items = []
        for o in self.owners:
            if names is None or o in names:
                items.append(f"{o}.*")
        for e in self.exprs:
            if names is not None and not any(v.name in names
                                             for v in E.vars_in(e)):
                continue
            if isinstance(e, (E.Var, E.Property, E.HasLabel)):
                items.append(e.cypher_repr())
            else:
                items.append(f"{type(e).__name__}({_owner(e) or e})")
        return "keeps=[" + ", ".join(sorted(items)) + "]"

    def __eq__(self, other):
        return isinstance(other, Required) and self.exprs == other.exprs \
            and self.owners == other.owners

    def __hash__(self):
        return hash((self.exprs, self.owners))

    def __repr__(self):
        return f"Required({self.describe()})"


def expr_reads(e: E.Expr, exprs: Set[E.Expr], owners: Set[str],
               bare_is_id: bool = False) -> None:
    """Add to ``exprs``/``owners`` every header entry ``e`` can read.
    Scopes of comprehension variables are ignored: a shadowed name only
    keeps more."""
    if isinstance(e, E.Var):
        if bare_is_id:
            exprs.add(e)
        else:
            owners.add(e.name)
        return
    if _owner(e) is not None:
        exprs.add(e)
        return
    if isinstance(e, E.HasType) and isinstance(e.rel, E.Var):
        exprs.add(E.Type(e.rel))  # as resolve_expr rewrites it
        return
    if isinstance(e, E.ExistsSubQuery):
        owners.update(v.name for v in E.vars_in(e))
        return
    ids = isinstance(e, _ID_ONLY_PARENTS) or (
        isinstance(e, (E.Equals, E.NotEquals))
        and isinstance(e.lhs, E.Var) and isinstance(e.rhs, E.Var))
    for c in e.children:
        if isinstance(c, E.Expr):
            expr_reads(c, exprs, owners, bare_is_id=ids)
        else:  # a tree node that is no expression: when in doubt
            owners.update(v.name for v in E.vars_in(e))


def _child_demands(op: R.RelationalOperator, req: Optional[Required]
                   ) -> Tuple[Optional[Required], ...]:
    """What ``op`` reads of each child, given what is read of ``op``."""
    t = type(op)
    if t is R.SelectOp:
        # the output is exactly what the names own
        return (Required(owners=op.names),)
    if t is R.AggregateOp:
        reads: List[E.Expr] = [a for _, a, _ in op.aggregations]
        owned = []
        for _, expr, _ in op.group:
            if isinstance(expr, E.Var):
                # _compute carries all it owns as `first` aggregates
                owned.append(expr.name)
            else:
                reads.append(expr)
        return (Required(owners=owned).reading(reads),)
    if req is None:
        return (None,) * len(op.children)
    if t is R.FilterOp:
        return (req.reading([op.predicate]),)
    if t is R.OrderByOp:
        return (req.reading([e for e, _ in op.items]),)
    if t in (R.SkipOp, R.LimitOp):
        return (req.reading([op.expr]),)
    if t is R.ProjectOp:
        return (req.reading([e for _, e, _ in op.items]),)
    if t is R.JoinOp:
        # one set for both sides: each header matches what it has
        both = req.reading((), ids_only=[e for p in op.pairs for e in p])
        return (both, both)
    # DistinctOp is over its whole header; every other operator is not
    # modelled: its children keep everything
    return (None,) * len(op.children)


def annotate_required(root: R.RelationalOperator) -> None:
    """Set ``op.required`` on every operator under ``root``.  A shared
    subtree (the planner memoises) takes the union of its parents'
    demands before the pass descends into it.  The pass never fails a
    query: on any exception the plan runs unpruned."""
    waiting: Dict[int, int] = {}        # id(op) -> parents not yet done
    ops: List[R.RelationalOperator] = []
    stack = [root]
    while stack:
        op = stack.pop()
        if id(op) in waiting:
            continue
        waiting[id(op)] = 0
        ops.append(op)
        stack.extend(op.children)
    for op in ops:
        for c in op.children:
            waiting[id(c)] += 1
    demand: Dict[int, Optional[Required]] = {id(root): None}
    ready = [root]
    try:
        while ready:
            op = ready.pop()
            op.required = demand[id(op)]
            for c, d in zip(op.children, _child_demands(op, op.required)):
                if id(c) in demand:
                    have = demand[id(c)]
                    demand[id(c)] = None if have is None else have.union(d)
                else:
                    demand[id(c)] = d
                waiting[id(c)] -= 1
                if not waiting[id(c)]:
                    ready.append(c)
    except Exception:
        for op in ops:
            op.required = None

"""CAPre adapted to JAX: static access analysis over jaxprs.

This is Algorithm 1 transposed onto the TPU stack (DESIGN.md section 2).
The "application" is a jitted step function; the "persistent objects" are
the parameter leaves; and the jaxpr — known entirely at compile time, like
the paper's Wala IR — tells us exactly which parameters each part of the
step touches:

  paper                        | here
  -----------------------------+------------------------------------------
  getfield navigation          | a jaxpr equation consuming a param leaf
  collection + loop iteration  | lax.scan over a stacked-layers param (xs)
  invokemethod augmentation    | recursion into pjit/remat/custom sub-jaxprs
  branch-dependent navigation  | params used under some lax.cond branches
  prefetching hints PH_m       | PrefetchPlan records ordered by first use

The plan drives the weight-streaming runtime (repro.runtime.prefetch): like
the paper's generated prefetch methods it is derived *before* execution and
adds zero runtime monitoring.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np
from jax._src.core import Literal as _Literal


def _lookup(env: dict, v):
    if isinstance(v, _Literal):
        return None
    return env.get(v)


@dataclass
class AccessRecord:
    path: str
    first_use: int  # program-order clock of the first consuming equation
    nbytes: int
    shape: tuple
    collection: bool = False  # scanned-over stacked array (CAPre collection)
    branch_dependent: bool = False  # used under a lax.cond branch (section 4.4)
    uses: int = 1

    def __repr__(self) -> str:
        tags = []
        if self.collection:
            tags.append("[]")
        if self.branch_dependent:
            tags.append("bd")
        return f"<{self.path}@{self.first_use} {self.nbytes}B {' '.join(tags)}>"


@dataclass
class PrefetchPlan:
    records: list[AccessRecord]

    @property
    def total_bytes(self) -> int:
        return sum(r.nbytes for r in self.records)

    def ordered(self) -> list[AccessRecord]:
        return sorted(self.records, key=lambda r: r.first_use)

    def collections(self) -> list[AccessRecord]:
        return [r for r in self.records if r.collection]

    def hints(self) -> list[str]:
        """String hints, CAPre-style."""
        return [
            r.path + ("[]" if r.collection else "") for r in self.ordered()
        ]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return ".".join(parts)


def build_access_plan(fn, params, *args, **kwargs) -> PrefetchPlan:
    """Trace ``fn(params, *args)`` and derive the parameter access plan.

    ``params`` may be concrete arrays or ShapeDtypeStructs (no allocation
    needed — same property as the paper's compile-time analysis)."""
    closed = jax.make_jaxpr(lambda p, *a: fn(p, *a, **kwargs))(params, *args)
    return plan_from_jaxpr(closed, params)


def plan_from_jaxpr(closed, params) -> PrefetchPlan:
    """The access plan from a step already traced to ``closed`` (a
    ``ClosedJaxpr`` whose leading inputs are ``params``' leaves): the walk
    half of :func:`build_access_plan`."""
    jaxpr = closed.jaxpr

    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    n_params = len(leaves)
    # the first n_params flattened invars belong to `params`
    var_info: dict = {}
    for (path, leaf), var in zip(leaves, jaxpr.invars[:n_params]):
        var_info[var] = _path_str(path)

    records: dict[str, AccessRecord] = {}
    clock = [0]
    use_log: list[set] = []  # per-branch used-path sets (for cond promotion)

    def record_use(pathname, aval, *, collection=False, branch=False):
        for s in use_log:
            s.add(pathname)
        r = records.get(pathname)
        nbytes = int(np.prod(aval.shape)) * aval.dtype.itemsize
        if r is None:
            records[pathname] = AccessRecord(
                path=pathname,
                first_use=clock[0],
                nbytes=nbytes,
                shape=tuple(aval.shape),
                collection=collection,
                branch_dependent=branch,
            )
        else:
            r.uses += 1
            r.collection |= collection
            # a use on an unconditional path clears branch-dependence
            # (the union-of-branches promotion of section 4.4)
            if not branch:
                r.branch_dependent = False

    def walk(jx, env: dict, in_branch: bool):
        """env maps jx's vars -> param path names."""
        for eqn in jx.eqns:
            clock[0] += 1
            prim = eqn.primitive.name
            sub = _sub_jaxpr(eqn)
            if prim == "scan" and sub is not None:
                n_consts = eqn.params["num_consts"]
                n_carry = eqn.params["num_carry"]
                body = sub
                body_env = {}
                for i, outer in enumerate(eqn.invars):
                    name = _lookup(env, outer)
                    if name is None:
                        continue
                    inner = body.invars[i]
                    if i >= n_consts + n_carry:
                        # scanned xs: the stacked-layers collection —
                        # every element will be accessed (CAPre collection)
                        record_use(name, outer.aval, collection=True, branch=in_branch)
                    body_env[inner] = name
                walk(body, body_env, in_branch)
            elif prim == "cond":
                branches = eqn.params["branches"]
                branch_used: list[set] = []
                for br in branches:
                    br_env = {}
                    # cond invars: (index, *operands)
                    for inner, outer in zip(br.jaxpr.invars, eqn.invars[1:]):
                        if _lookup(env, outer) is not None:
                            br_env[inner] = env[outer]
                    use_log.append(set())
                    walk(br.jaxpr, br_env, True)
                    branch_used.append(use_log.pop())
                # section 4.4 promotion: a param accessed in EVERY branch is
                # not branch-dependent ("the accessed objects are the same
                # although the methods executed may differ")
                in_all = set.intersection(*branch_used) if branch_used else set()
                for pathname in in_all:
                    if pathname in records and not in_branch:
                        records[pathname].branch_dependent = False
            elif sub is not None:
                sub_env = {}
                for inner, outer in zip(sub.invars, eqn.invars):
                    if _lookup(env, outer) is not None:
                        sub_env[inner] = env[outer]
                walk(sub, sub_env, in_branch)
            else:
                for v in eqn.invars:
                    name = _lookup(env, v)
                    if name is not None:
                        record_use(name, v.aval, branch=in_branch)

    env0 = dict(var_info)
    walk(jaxpr, env0, False)
    return PrefetchPlan(records=list(records.values()))


def _sub_jaxpr(eqn):
    p = eqn.primitive.name
    params = eqn.params
    if p == "scan":
        return params["jaxpr"].jaxpr
    if p in ("pjit", "closed_call", "remat2", "remat", "checkpoint", "custom_vjp_call_jaxpr"):
        j = params.get("jaxpr") or params.get("call_jaxpr") or params.get("fun_jaxpr")
        return getattr(j, "jaxpr", j) if j is not None else None
    if p in ("custom_jvp_call", "custom_vjp_call"):
        j = params.get("call_jaxpr") or params.get("fun_jaxpr")
        return getattr(j, "jaxpr", j) if j is not None else None
    if p == "shard_map":
        j = params.get("jaxpr")
        return getattr(j, "jaxpr", j) if j is not None else None
    if p == "while":
        return params["body_jaxpr"].jaxpr
    return None


def rop_plan(params, depth_groups: int) -> PrefetchPlan:
    """The ROP baseline on the tensor store: schema-only — prefetch the
    first ``depth_groups`` top-level parameter groups in tree order,
    never 'collections' (it cannot know a scan consumes all layers).
    Mirrors the paper's depth-limited referenced-object expansion."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    groups: dict[str, list] = {}
    for path, leaf in leaves:
        top = _path_str(path).split(".")[0]
        groups.setdefault(top, []).append((path, leaf))
    records = []
    for gi, (gname, members) in enumerate(groups.items()):
        if gi >= depth_groups:
            break
        for path, leaf in members:
            records.append(
                AccessRecord(
                    path=_path_str(path),
                    first_use=gi,
                    nbytes=int(np.prod(leaf.shape)) * leaf.dtype.itemsize,
                    shape=tuple(leaf.shape),
                )
            )
    return PrefetchPlan(records=records)

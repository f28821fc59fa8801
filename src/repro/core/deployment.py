"""Unified deployment API: put a model on the emulated CIM macro, once.

Unicorn-CIM's co-design insight is that protection should be spent where
sensitivity lives — exponent bits, and by extension the layers whose exponent
distributions matter most. A :class:`ReliabilityPolicy` expresses exactly
that: an ordered list of pytree-path rules (glob or regex, first match wins,
with a default rule) mapping each weight matrix to its own protection level
(``protect`` ∈ {none, one4n, per_weight}), injection field, BER scale, number
format and grouping — so e.g. the unembed gets One4N while MLP mantissas go
unprotected, in ONE deployment.

The policy compiles into a pytree-registered :class:`CIMDeployment` that owns
the packed stores and passthrough leaves, optional mesh placement, fault
state and cumulative ECC statistics, and exposes the whole lifecycle::

    policy = ReliabilityPolicy(
        rules=(PolicyRule("unembed", protect="one4n"),
               PolicyRule("embed",   protect="per_weight"),
               PolicyRule("*mlp*",   protect="none", field="mantissa")),
        default=PolicyRule(deploy=False))
    dep = CIMDeployment.deploy(params, policy)      # align + pack per rule
    dep = dep.shard(mesh)                           # optional mesh placement
    dep = dep.inject(key, ber)                      # static soft errors
    logits = dep.linear(x, "unembed")               # auto-dispatched matmul
    restored, stats = dep.read()                    # decode + ECC stats

``linear`` dispatches automatically from the store's placement and dtype
(see :func:`dispatch_linear`):

    ==========================  =============================================
    store placement / dtype      route
    ==========================  =============================================
    mesh with a "model" axis    ``cim_linear_store_sharded`` — shard_map'd
                                fused kernel, one shard per macro column
                                group (falls through to the rows below when
                                the store cannot shard or tile)
    fp16, one4n/none            ``cim_linear_store`` — fused Pallas decode-
                                on-read kernel, packed planes straight to
                                VMEM
    per_weight / non-fp16       GSPMD reference path (packed jnp decode
                                fused by XLA into the matmul)
    rule.serve_path == 'hbm'    decode once to fp16, plain ``x @ w``
    passthrough leaf            plain ``x @ w``
    ==========================  =============================================

Counter-PRNG contract: ``CIMDeployment.inject`` splits its key across the
flat leaves of the deployment exactly like the legacy ``cim.inject_pytree``,
so a mixed-protection policy deployment is bit-identical — stores, inject
streams, decoded reads, ECC stats — to manually composing per-leaf
``deploy_pytree`` calls with the same per-rule configs (tested in
``tests/test_deployment.py``, single-device and on a forced-8-device mesh).

``cim.deploy_pytree`` / ``inject_pytree`` / ``read_pytree`` remain as
deprecation shims forwarding here.
"""
from __future__ import annotations

import dataclasses
import fnmatch
import re
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import align as align_lib
from repro.core import cim as cim_lib
from repro.core import faultmodels as fm_lib
from repro.core.bitops import FORMATS

# ---------------------------------------------------------------------------
# Validated vocabularies of every enum-like policy field. A typo like
# protect="one4N" must fail at construction with a clear message, not deep
# inside cim.py.
# ---------------------------------------------------------------------------

VALID_PROTECTS = ("one4n", "per_weight", "none")
VALID_FIELDS = ("full", "mantissa", "exponent_sign")
VALID_SERVE_PATHS = ("fused", "hbm")
VALID_MODES = ("off", "align", "cim")
VALID_INJECTS = ("static", "dynamic")


def check_enum(name: str, value, allowed: Sequence[str], where: str) -> None:
    """Raise ``ValueError`` with the allowed vocabulary on a bad enum value."""
    if value not in allowed:
        raise ValueError(
            f"{where}: {name}={value!r} is not valid; expected one of "
            f"{', '.join(repr(a) for a in allowed)}")


def path_str(path) -> str:
    """A ``tree_flatten_with_path`` key path as a '/'-joined match string.

    ``{'groups': {'blk0': {'attn': {'wq': ...}}}}`` flattens to
    ``"groups/blk0/attn/wq"`` — the string policy rules glob against.
    """
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


@dataclasses.dataclass(frozen=True)
class PolicyRule:
    """One per-layer reliability setting, keyed by a pytree-path pattern.

    ``pattern`` is an ``fnmatch`` glob against the '/'-joined leaf path
    (``"unembed"``, ``"groups/*/attn/*"``); prefix with ``re:`` for a full
    regex (``"re:.*mlp\\.(w1|w2)"``). Matching is whole-string for globs
    unless the pattern contains no wildcard, in which case it matches any
    path *segment* equal to it (so ``"embed"`` hits ``"embed"`` but not
    ``"unembed"``).

    ``deploy=False`` makes matching leaves pass through undeployed;
    ``ber_scale`` scales the deployment-level BER for matching stores (cells
    with tighter retention margins); ``field`` restricts which stored cells
    the faults land in.
    """

    pattern: str = "*"
    deploy: bool = True
    protect: str = "one4n"           # one4n | per_weight | none
    field: str = "full"              # full | mantissa | exponent_sign
    ber_scale: float = 1.0
    n_group: int = 8
    index: int = 2
    row_weights: int = 16
    fmt_name: str = "fp16"
    serve_path: str = "fused"        # fused | hbm
    row_cache: bool = True           # fused static serving: materialize the
                                     # decoded-row cache at serving_params
                                     # time (hot full-matrix reads, e.g. the
                                     # unembed projection). Leaves served by
                                     # sparse row gathers (embed tables)
                                     # should opt out — the packed image is
                                     # the whole point there.
    fault_model: str = ""            # error process of matching stores
                                     # (repro.core.faultmodels grammar, e.g.
                                     # "burst:rate=0.3,axis=col"); "" means
                                     # the deployment-level model (i.i.d. by
                                     # default)

    def __post_init__(self):
        where = f"PolicyRule(pattern={self.pattern!r})"
        check_enum("protect", self.protect, VALID_PROTECTS, where)
        check_enum("field", self.field, VALID_FIELDS, where)
        check_enum("serve_path", self.serve_path, VALID_SERVE_PATHS, where)
        check_enum("fmt_name", self.fmt_name, tuple(FORMATS), where)
        if self.ber_scale < 0:
            raise ValueError(f"{where}: ber_scale must be >= 0, "
                             f"got {self.ber_scale}")
        fm_lib.parse_fault_model(self.fault_model)   # validate eagerly

    @property
    def fault_process(self):
        """Parsed :class:`~repro.core.faultmodels.FaultProcess` (or None)."""
        return fm_lib.parse_fault_model(self.fault_model)

    @property
    def fmt(self):
        return FORMATS[self.fmt_name]

    @property
    def cim_cfg(self) -> cim_lib.CIMConfig:
        return cim_lib.CIMConfig(n_group=self.n_group, index=self.index,
                                 protect=self.protect, fmt=self.fmt,
                                 row_weights=self.row_weights)

    @property
    def align_cfg(self) -> align_lib.AlignmentConfig:
        return align_lib.AlignmentConfig(n_group=self.n_group,
                                         index=self.index, fmt=self.fmt)

    def matches(self, leaf_path: str) -> bool:
        if self.pattern.startswith("re:"):
            return re.fullmatch(self.pattern[3:], leaf_path) is not None
        if not any(c in self.pattern for c in "*?["):
            return self.pattern == leaf_path or \
                self.pattern in leaf_path.split("/")
        return fnmatch.fnmatchcase(leaf_path, self.pattern)


@dataclasses.dataclass(frozen=True)
class ReliabilityPolicy:
    """Ordered pytree-path rules (first match wins) plus a default rule.

    The default rule catches every leaf no rule matches; a policy with no
    ``rules`` applies the default uniformly — that is exactly what the legacy
    one-global-``CIMConfig`` API could express
    (:attr:`repro.core.api.ReliabilityConfig.policy` builds it).
    """

    rules: Tuple[PolicyRule, ...] = ()
    default: PolicyRule = PolicyRule()

    def __post_init__(self):
        object.__setattr__(self, "rules", tuple(self.rules))
        for r in tuple(self.rules) + (self.default,):
            if not isinstance(r, PolicyRule):
                raise TypeError(f"policy rules must be PolicyRule, got "
                                f"{type(r).__name__}")

    def rule_for(self, leaf_path: str) -> PolicyRule:
        for rule in self.rules:
            if rule.matches(leaf_path):
                return rule
        return self.default

    @property
    def uniform(self) -> bool:
        """True when every leaf sees the same settings (no per-layer rules)."""
        return not self.rules

    def deploy(self, params, predicate=None) -> "CIMDeployment":
        return CIMDeployment.deploy(params, self, predicate=predicate)


# single definition of leaf deployability, shared with the legacy cim shims
_deployable = cim_lib._deployable


def _zero_stats():
    return {"corrected": jnp.zeros((), jnp.int32),
            "uncorrectable": jnp.zeros((), jnp.int32)}


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(eq=False)
class CIMDeployment:
    """A model deployed on the emulated macro under a reliability policy.

    Children: ``stores`` (the params pytree with deployed leaves replaced by
    packed :class:`~repro.core.cim.CIMStore`\\ s) and ``ecc_stats``
    (cumulative corrected/uncorrectable counters, accumulated by ``read``).
    Aux: the policy, the per-flat-leaf rule/path assignment, and the mesh
    placement — all hashable, so a deployment passes through ``jax.jit``.
    """

    stores: object
    ecc_stats: dict
    policy: ReliabilityPolicy
    rules: Tuple[Optional[PolicyRule], ...]   # per flat leaf; None=passthrough
    paths: Tuple[str, ...]
    placement: Optional[tuple] = None         # (mesh, axis, dim) or None

    def tree_flatten(self):
        return ((self.stores, self.ecc_stats),
                (self.policy, self.rules, self.paths, self.placement))

    @classmethod
    def tree_unflatten(cls, aux, children):
        stores, ecc_stats = children
        policy, rules, paths, placement = aux
        return cls(stores, ecc_stats, policy, rules, paths, placement)

    # ------------------------------------------------------------ lifecycle

    @classmethod
    def deploy(cls, params, policy: ReliabilityPolicy,
               predicate: Optional[Callable] = None) -> "CIMDeployment":
        """Align + pack every leaf per its first matching rule.

        A leaf is deployed when its rule says ``deploy=True``, it is a 2-D
        float matrix, and ``predicate(path, leaf)`` (if given) holds; every
        other leaf passes through untouched. Per-leaf packing is identical to
        ``cim.deploy_pytree`` with the rule's config, so mixed policies are
        bit-identical to manual per-leaf composition.
        """
        leaves_wp, treedef = jax.tree_util.tree_flatten_with_path(params)
        out, rules, paths = [], [], []
        for path, leaf in leaves_wp:
            p = path_str(path)
            rule = policy.rule_for(p)
            paths.append(p)
            if rule.deploy and _deployable(path, leaf) and \
                    (predicate is None or predicate(path, leaf)):
                w_al, _ = align_lib.align_matrix(leaf, rule.align_cfg)
                out.append(cim_lib.pack(w_al, rule.cim_cfg))
                rules.append(rule)
            else:
                out.append(leaf)
                rules.append(None)
        return cls(stores=jax.tree_util.tree_unflatten(treedef, out),
                   ecc_stats=_zero_stats(), policy=policy,
                   rules=tuple(rules), paths=tuple(paths))

    @property
    def mesh(self):
        return self.placement[0] if self.placement else None

    def _flat(self):
        return jax.tree_util.tree_flatten(self.stores,
                                          is_leaf=cim_lib._is_store)

    def _replace_stores(self, stores) -> "CIMDeployment":
        # each derived deployment owns its cumulative counters — reads on one
        # branch must not bleed into siblings or the base
        return CIMDeployment(stores, dict(self.ecc_stats), self.policy,
                             self.rules, self.paths, self.placement)

    def store_leaves(self):
        """[(path, rule, store)] of the deployed leaves, tree order."""
        flat, _ = self._flat()
        return [(p, r, s) for p, r, s in zip(self.paths, self.rules, flat)
                if cim_lib._is_store(s)]

    # ------------------------------------------------------------ fault state

    def inject(self, key, ber, field: Optional[str] = None,
               request_id: Optional[int] = None,
               model=None) -> "CIMDeployment":
        """Fresh soft errors into every store at ``ber * rule.ber_scale`` in
        the rule's ``field`` (or the ``field`` override for all stores).

        The key splits across the flat leaves exactly like the legacy
        ``cim.inject_pytree``; sharded placements route through
        ``cim.inject_sharded`` (bit-identical streams, PR-3 contract).
        ``request_id`` folds the key per serving request before the split, so
        a request-scoped static image draws the same streams no matter which
        engine slot (or co-batch) serves it.

        ``model`` (a :class:`~repro.core.faultmodels.FaultProcess` or grammar
        string) selects the error process for every store; per-rule
        ``fault_model`` settings fill in where no override is given. The
        default i.i.d. process reproduces the legacy streams bit for bit.
        """
        if field is not None:
            # a Fig. 2 axis like 'exponent' would silently inject NOTHING
            # downstream (both cim.inject threshold gates test False)
            check_enum("field", field, VALID_FIELDS, "CIMDeployment.inject")
        model = fm_lib.parse_fault_model(model)
        if request_id is not None:
            key = jax.random.fold_in(key, request_id)
        flat, treedef = self._flat()
        keys = jax.random.split(key, len(flat))
        out = []
        for k, leaf, rule in zip(keys, flat, self.rules):
            if cim_lib._is_store(leaf):
                leaf_ber = ber * rule.ber_scale
                leaf_field = field if field is not None else rule.field
                leaf_model = model if model is not None else rule.fault_process
                out.append(self._inject_one(k, leaf, leaf_ber, leaf_field,
                                            leaf_model))
            else:
                out.append(leaf)
        return self._replace_stores(jax.tree_util.tree_unflatten(treedef, out))

    def _inject_one(self, key, store, ber, field, model=None):
        if self.placement is not None:
            mesh, axis, dim = self.placement
            n_sh = int(mesh.shape[axis]) if axis in mesh.axis_names else 1
            if n_sh > 1 and cim_lib.can_shard_store(store, n_sh, dim):
                return cim_lib.inject_sharded(key, store, ber, field,
                                              mesh=mesh, axis=axis, dim=dim,
                                              model=model)
        return cim_lib.inject(key, store, ber, field, model=model)

    def runtime(self, key, ber, field: str = "full", model=None) -> dict:
        """Per-read dynamic-injection runtime (the ``_cim`` entry the serving
        model folds per leaf and per read index): base counter-PRNG plane
        seeds plus per-cell-class Bernoulli thresholds.

        ``model`` (process or grammar string) rides along as static pytree
        structure; serving reads compile it to per-element thresholds — drift
        keys its tick on the request-local read position."""
        from repro.kernels.fault_inject.ops import ber_to_threshold
        check_enum("field", field, VALID_FIELDS, "CIMDeployment.runtime")
        thr = ber_to_threshold(ber)
        zero = jnp.uint32(0)
        rt = {"seeds": cim_lib.plane_seeds(key),
              "thr_man": thr if field in ("full", "mantissa") else zero,
              "thr_meta": thr if field in ("full", "exponent_sign") else zero}
        model = fm_lib.parse_fault_model(model)
        if model is not None and model.kind != "iid":
            rt["model"] = model
        return rt

    # ------------------------------------------------------------ read paths

    def _accumulate(self, stats) -> None:
        # Cumulative ECC accounting. Eager calls fold into the running
        # counters in place; under a trace the counters cannot absorb tracer
        # values, so traced reads simply return their stats to the caller.
        if any(isinstance(v, jax.core.Tracer) for v in stats.values()) or \
                any(isinstance(v, jax.core.Tracer)
                    for v in self.ecc_stats.values()):
            return
        for k_ in ("corrected", "uncorrectable"):
            self.ecc_stats[k_] = self.ecc_stats[k_] + stats[k_]

    def read(self):
        """Decode every store -> (params pytree, {'corrected','uncorrectable'}).

        Eager reads also fold the stats into the deployment's cumulative
        ``ecc_stats`` counters."""
        flat, treedef = self._flat()
        out, stats = [], _zero_stats()
        for leaf in flat:
            if cim_lib._is_store(leaf):
                w, st = cim_lib.read(leaf)
                out.append(w)
                stats = {k_: stats[k_] + st[k_] for k_ in stats}
            else:
                out.append(leaf)
        self._accumulate(stats)
        return jax.tree_util.tree_unflatten(treedef, out), stats

    def stats(self) -> dict:
        """Aggregate ECC status counts without reconstructing any weights."""
        agg = _zero_stats()
        for _, _, s in self.store_leaves():
            st = cim_lib.store_stats(s)
            agg = {k_: agg[k_] + st[k_] for k_ in agg}
        return agg

    def _leaf(self, path: str):
        for i, p in enumerate(self.paths):
            if p == path:
                return self._flat()[0][i], self.rules[i]
        raise KeyError(f"no leaf at path {path!r}; deployment has "
                       f"{sorted(self.paths)}")

    def read_rows(self, idx, path: str = "embed", *, seeds=None, thr_man=0,
                  thr_meta=0, model=None):
        """Decode-on-read row gather of the store at ``path`` (embedding
        serving: only the gathered rows' codewords are decoded). ``seeds``
        (see ``cim.plane_seeds``) turns on per-read dynamic injection;
        ``model`` shapes it into a structured error process."""
        leaf, _ = self._leaf(path)
        if not cim_lib._is_store(leaf):
            return jnp.asarray(leaf, jnp.float32)[idx]
        return cim_lib.read_rows(leaf, idx, seeds=seeds, thr_man=thr_man,
                                 thr_meta=thr_meta, model=model)

    def linear(self, x, path: str, *, scalars=None, request=None, runtime=None,
               with_info: bool = False, model=None):
        """``x [..., K] @ leaf(path) -> [..., J]``, route auto-dispatched.

        A passthrough leaf is a plain matmul. A store follows the module
        dispatch table (:func:`dispatch_linear`) — fused Pallas, sharded
        shard_map, or the GSPMD reference — except when its rule pins
        ``serve_path='hbm'``, which decodes once and matmuls the fp16 copy
        (stats fold into the cumulative ECC counters on eager calls).

        ``request=(req_salt, pos)`` with a ``runtime`` (see :meth:`runtime`)
        derives per-request dynamic-injection scalars for this read —
        counter-PRNG streams keyed by (leaf, request, read index), the
        serving engine's batch-invariance contract. Mutually exclusive with
        an explicit ``scalars`` vector.
        """
        if request is not None:
            if scalars is not None:
                raise ValueError(
                    f"linear({path!r}): pass either scalars= or request=, "
                    f"not both")
            if runtime is None:
                raise ValueError(
                    f"linear({path!r}): request= needs the runtime= dict "
                    f"(see CIMDeployment.runtime)")
            from repro.kernels.cim_read import ops as cr_ops
            req_salt, pos = request
            seeds = request_read_seeds(runtime["seeds"], leaf_salt(path),
                                       req_salt, pos)
            model = runtime.get("model")
            # drift keys its tick on the request-local read position; the
            # thresholds absorb the time scaling here, so the model handed
            # downstream carries tick=0 (no double scaling)
            thr_man = fm_lib.compiled_threshold(model, runtime["thr_man"],
                                                tick=pos)
            thr_meta = fm_lib.compiled_threshold(model, runtime["thr_meta"],
                                                 tick=pos)
            if model is not None and model.kind == "drift":
                model = dataclasses.replace(model, tick=0)
            scalars = cr_ops.make_scalars(seeds, thr_man, thr_meta,
                                          model=model)
        leaf, rule = self._leaf(path)
        if not cim_lib._is_store(leaf):
            if scalars is not None:
                raise ValueError(
                    f"linear({path!r}): scalars (per-read dynamic injection) "
                    f"given, but the leaf is a passthrough — no stored cells "
                    f"to fault")
            out = x @ leaf.astype(x.dtype)
            return (out, {"route": "passthrough"}) if with_info else out
        if rule.serve_path == "hbm":
            if scalars is not None:
                raise ValueError(
                    f"linear({path!r}): scalars given, but the rule pins "
                    f"serve_path='hbm' (decode-once) — per-read dynamic "
                    f"injection only exists on the fused/GSPMD routes")
            w, st = cim_lib.read(leaf)
            self._accumulate(st)
            out = x.astype(jnp.float32) @ w
            return (out, {"route": "hbm"}) if with_info else out
        _, axis, dim = self.placement or (None, "model", "j")
        return dispatch_linear(x, leaf, scalars=scalars, mesh=self.mesh,
                               axis=axis, dim=dim, with_info=with_info,
                               model=model)

    # ------------------------------------------------------------ placement

    def shard(self, mesh, *, axis: str = "model", dim: str = "j"
              ) -> "CIMDeployment":
        """Mesh placement: every store's packed planes split over ``axis``
        along ``dim`` (one shard ≈ one macro column group,
        ``cim.shard_store``); every passthrough leaf replicated. Subsequent
        ``inject`` calls draw per-shard counter-PRNG streams at global store
        coordinates; ``linear`` routes through the shard_map'd fused kernel."""
        stores = place_stores(self.stores, mesh, axis=axis, dim=dim)
        return CIMDeployment(stores, dict(self.ecc_stats), self.policy,
                             self.rules, self.paths, (mesh, axis, dim))

    # ------------------------------------------------------------ serving

    def serving_params(self, *, dynamic_key=None, ber: float = 0.0,
                       field: str = "full", row_cache: bool = True,
                       model=None):
        """The params pytree handed to the jitted model steps.

        Fused rules keep their stores packed; ``serve_path='hbm'`` rules are
        decoded to fp16 up front (stats fold into ``ecc_stats``). With
        ``dynamic_key`` set, the ``_cim`` per-read dynamic-injection runtime
        rides along (dict pytrees only).

        Static fused serving additionally warms the **decoded-row cache** on
        stores whose rule has ``row_cache=True``: ``store.cache`` is set to
        the jit-decoded fp32 matrix, and :func:`dispatch_linear` /
        :func:`dispatch_read_rows` consult it instead of re-decoding per
        step. The packed planes stay authoritative (ECC stats keep reading
        the SRAM image), every ``inject`` rebuilds stores cache-less (so a
        stale cache cannot survive a fault refresh), and dynamic per-request
        streams bypass the cache entirely — pass ``row_cache=False`` to
        disable warming outright.
        """
        static = not (dynamic_key is not None and ber > 0)
        flat, treedef = self._flat()
        out = []
        for leaf, rule in zip(flat, self.rules):
            if cim_lib._is_store(leaf) and rule.serve_path == "hbm":
                w, st = cim_lib.read(leaf)
                self._accumulate(st)
                out.append(w)
            elif (cim_lib._is_store(leaf) and rule.serve_path == "fused"
                  and row_cache and rule.row_cache and static
                  and leaf.cache is None):
                out.append(dataclasses.replace(leaf, cache=_read_w_jit(leaf)))
            else:
                out.append(leaf)
        params = jax.tree_util.tree_unflatten(treedef, out)
        if dynamic_key is not None and ber > 0:
            if not isinstance(params, dict):
                raise TypeError("dynamic serving runtime needs a dict params "
                                f"pytree, got {type(params).__name__}")
            params = dict(params)
            rt = self.runtime(dynamic_key, ber, field, model=model)
            if self.placement is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                rep = NamedSharding(self.placement[0], P())
                rt = jax.tree_util.tree_map(
                    lambda a: jax.device_put(a, rep), rt)
            params["_cim"] = rt
        return params

    # ------------------------------------------------------------ accounting

    def bit_cost(self) -> dict:
        """Stored-cell cost of the deployment — the policy search's axis.

        ``stored_bits`` counts logical SRAM cells across every deployed store
        (:attr:`~repro.core.cim.CIMStore.stored_bits`: codewords at
        ``code.n`` bits, signs once); ``raw_bits`` is the unencoded
        ``K*J*fmt.total_bits`` of the same leaves, so ``overhead`` is the
        ECC/packing cost the paper reports (~8.98% for One4N fp16 N=8).
        Passthrough leaves cost nothing (they are not on the macro).
        """
        stored = raw = byts = 0
        for _, rule, s in self.store_leaves():
            stored += s.stored_bits
            raw += int(np.prod(s.shape)) * rule.fmt.total_bits
            byts += s.stored_bytes
        return {"stored_bits": int(stored), "raw_bits": int(raw),
                "stored_bytes": int(byts),
                "overhead": (stored / raw - 1.0) if raw else 0.0}

    # ------------------------------------------------------------ reporting

    def report(self) -> str:
        """One line per deployed leaf: path, rule, image bytes."""
        lines = []
        for p, rule, s in self.store_leaves():
            lines.append(
                f"{p}: protect={rule.protect} field={rule.field} "
                f"ber_scale={rule.ber_scale:g} fmt={rule.fmt_name} "
                f"N={rule.n_group} {s.shape[0]}x{s.shape[1]} "
                f"packed={s.stored_bytes}B")
        if not lines:
            return "(no deployed leaves)"
        return "\n".join(lines)


def place_stores(stores, mesh, *, axis: str = "model", dim: str = "j"):
    """Mesh placement of a stores pytree: every packed store split over
    ``axis`` along ``dim`` (``cim.shard_store``, replication degrade per
    plane); every other leaf replicated. The single placement rule behind
    ``CIMDeployment.shard`` and ``launch.serve.place_on_mesh``."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())

    def place(leaf):
        if cim_lib._is_store(leaf):
            return cim_lib.shard_store(leaf, mesh, axis=axis, dim=dim)
        return jax.device_put(leaf, rep)

    return jax.tree_util.tree_map(place, stores, is_leaf=cim_lib._is_store)


# ---------------------------------------------------------------------------
# Expert-parallel MoE deployment: each expert is its own macro.
# ---------------------------------------------------------------------------

# the stacked MoE expert tensors ([E, D, F] per block, [G, E, D, F] under
# group-scan stacking) — >2-D, so the plain CIMDeployment never touches them
EXPERT_LEAF_NAMES = ("moe_win", "moe_wgate", "moe_wout")


@dataclasses.dataclass(eq=False)
class ExpertDeployment:
    """Per-expert CIM deployment of a model's stacked MoE weights.

    Physically each expert's matrices live on their own macro (that is what
    expert parallelism shards), so each expert can carry its own protection
    level and BER scale. This class slices every stacked expert tensor
    (:data:`EXPERT_LEAF_NAMES`, ``[E, D, F]`` or group-stacked
    ``[G, E, D, F]``) into per-expert 2-D matrices at paths like
    ``groups/blk0/moe_win/g0/expert3`` and deploys them through one
    :class:`CIMDeployment` — :class:`ReliabilityPolicy` rules match the
    per-expert paths (``PolicyRule("*/expert3", ber_scale=4.0)`` targets one
    weak expert across all its matrices).

    Serving is decode-once (hbm-style): :meth:`serving_params` reads every
    expert store back, restacks the dense tensors in the model's dtype, and
    the existing ``moe`` / ``moe_a2a`` dispatch consumes them unchanged — the
    a2a all-to-all IS the expert-parallel routing; this class only decides
    what image those expert weights were read from. Injection is therefore
    **static only**: faults flip each expert's packed image once, and every
    read of the restacked tensor sees the same faulted weights (which keeps
    the engine's bitwise solo-vs-cobatched guarantee intact — the faults are
    a deterministic property of the image, not of the read). Per-read
    dynamic streams would need a per-expert fused-read path inside the
    dispatch kernels; that is out of scope here.

    ECC accounting is per expert: :meth:`stats_by_expert` exposes each
    expert store's corrected/uncorrectable counters (the serving launcher's
    ``--expert-cim`` artifact).
    """

    inner: CIMDeployment
    leaves: Tuple[Tuple[str, tuple], ...]   # (params path, stacked shape)

    @classmethod
    def deploy(cls, params, policy: ReliabilityPolicy) -> "ExpertDeployment":
        """Slice + deploy every stacked expert tensor of ``params``.

        Raises if ``params`` has no expert leaves (deploying nothing would
        silently serve unprotected experts)."""
        leaves_wp, _ = jax.tree_util.tree_flatten_with_path(
            params, is_leaf=cim_lib._is_store)
        expert_params, meta = {}, []
        for path, leaf in leaves_wp:
            p = path_str(path)
            if cim_lib._is_store(leaf) or \
                    p.split("/")[-1] not in EXPERT_LEAF_NAMES:
                continue
            if getattr(leaf, "ndim", 0) == 4:      # [G, E, D, F]
                expert_params[p] = {
                    f"g{g}": {f"expert{e}": leaf[g, e]
                              for e in range(leaf.shape[1])}
                    for g in range(leaf.shape[0])}
            elif getattr(leaf, "ndim", 0) == 3:    # [E, D, F]
                expert_params[p] = {f"expert{e}": leaf[e]
                                    for e in range(leaf.shape[0])}
            else:
                continue
            meta.append((p, tuple(leaf.shape)))
        if not expert_params:
            raise ValueError(
                "ExpertDeployment.deploy: params has no stacked MoE expert "
                f"leaves (looked for {', '.join(EXPERT_LEAF_NAMES)})")
        return cls(inner=CIMDeployment.deploy(expert_params, policy),
                   leaves=tuple(meta))

    def inject(self, key, ber, field: Optional[str] = None,
               model=None) -> "ExpertDeployment":
        """Static soft errors into every expert store (per-rule BER scales
        apply, so a per-expert rule can age one expert harder)."""
        return ExpertDeployment(
            inner=self.inner.inject(key, ber, field=field, model=model),
            leaves=self.leaves)

    def serving_params(self, params):
        """Decode every expert store once and restack the dense tensors into
        ``params`` (the model's moe/moe_a2a dispatch consumes them as-is).

        ``params`` may already be a fused/hbm serving pytree — store leaves
        and the ``_cim`` runtime pass through untouched; only the expert
        leaf paths recorded at deploy time are replaced. ECC stats of the
        read fold into the inner deployment's cumulative counters.
        """
        decoded, _ = self.inner.read()
        shapes = dict(self.leaves)
        leaves_wp, treedef = jax.tree_util.tree_flatten_with_path(
            params, is_leaf=cim_lib._is_store)
        out = []
        for path, leaf in leaves_wp:
            p = path_str(path)
            if p not in shapes or cim_lib._is_store(leaf):
                out.append(leaf)
                continue
            shape, sub = shapes[p], decoded[p]
            if len(shape) == 4:
                w = jnp.stack([
                    jnp.stack([sub[f"g{g}"][f"expert{e}"]
                               for e in range(shape[1])])
                    for g in range(shape[0])])
            else:
                w = jnp.stack([sub[f"expert{e}"] for e in range(shape[0])])
            out.append(w.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    def stats_by_expert(self) -> dict:
        """Per-expert-store ECC counters: path -> counts + rule settings."""
        out = {}
        for p, rule, s in self.inner.store_leaves():
            st = cim_lib.store_stats(s)
            out[p] = {"corrected": int(st["corrected"]),
                      "uncorrectable": int(st["uncorrectable"]),
                      "protect": rule.protect,
                      "ber_scale": rule.ber_scale}
        return out

    def report(self) -> str:
        return self.inner.report()


# ---------------------------------------------------------------------------
# Per-request counter-PRNG key derivation (the serving engine's contract).
#
# A dynamic-injection read's flip streams are keyed by the chain
#
#   plane seed --fold leaf_salt--> --fold request_salt--> --fold pos--> seed
#
# where ``pos`` is the REQUEST-LOCAL read index (its decode position), never
# an engine-global step. Every link is cim.fold_seed, so a request's fault
# streams depend only on (deployment key, leaf, request id, position) — bit-
# identical whether the request is served alone or continuously co-batched,
# and on any engine slot. With no request salt the chain degrades to the
# PR-2 single-stream serving contract (fold leaf, fold pos).
#
# Two salt families fill the ``request`` link, both REPLICA-INVARIANT (they
# derive from globally-assigned request ids or prompt content, never from a
# slot index, replica name, mesh, or engine step — the fleet router's bitwise
# replica-invariance contract rests on this):
#
#   * ``request_salt(rid)`` — decode (generation) reads: each request draws
#     its own soft-error streams while generating;
#   * ``prefix_salt(tokens)`` — prompt-prefill reads: the salt is a hash of
#     the token *content* up through the chunk being prefilled, so two
#     requests sharing a prompt prefix draw bit-identical fault streams over
#     it. That is what makes prefix/KV-cache reuse exact under per-request
#     dynamic injection: a cached prefix chunk's KV equals what a cold
#     prefill of the same tokens would compute, to the bit.
# ---------------------------------------------------------------------------

# distinct per-leaf salts: each CIM-deployed matrix is its own macro and must
# draw independent fault streams (mirrors inject_pytree's per-store key split)
CIM_LEAF_SALTS = {"embed": 0x1001, "unembed": 0x2002}

_REQUEST_SALT_CONST = 0x7FEED5A1
_PREFIX_SALT_CONST = 0x5EEDC0DE


def leaf_salt(path: str) -> int:
    """The per-macro seed salt of a deployed leaf. The embed/unembed table
    keeps the PR-2 serving streams bit-stable; any other path hashes to a
    deterministic uint32 (FNV-1a over the path string)."""
    if path in CIM_LEAF_SALTS:
        return CIM_LEAF_SALTS[path]
    h = 0x811C9DC5
    for ch in path.encode():
        h = ((h ^ ch) * 0x01000193) & 0xFFFFFFFF
    return h


def request_salt(request_id: int):
    """uint32 counter-PRNG salt of a serving request id (engine slots fold it
    into every CIM read seed — slot index never enters the chain)."""
    return cim_lib.fold_seed(jnp.uint32(_REQUEST_SALT_CONST), request_id)


def prefix_salt(tokens) -> int:
    """Content salt of a prompt prefix: deterministic uint32 FNV-1a over the
    token ids (as little-endian uint32 words), seeded off its own constant so
    prefix streams never alias the ``request_salt`` family.

    The serving engine salts every prompt-prefill CIM read with the salt of
    the tokens *up through that chunk* — a pure function of prompt content,
    independent of request id, slot, replica, and arrival order. Cold
    prefill is therefore deterministic in content, and a prefix-cache hit
    (reusing another request's prefilled KV for the same tokens) is bitwise
    identical to recomputing."""
    h = (0x811C9DC5 ^ _PREFIX_SALT_CONST) & 0xFFFFFFFF
    for b in np.asarray(tokens, np.uint32).tobytes():
        h = ((h ^ b) * 0x01000193) & 0xFFFFFFFF
    return h


def request_read_seeds(seeds: dict, leaf_salt_: int, req_salt, pos) -> dict:
    """Fold base plane seeds down to one (leaf, request, read) stream set.

    ``req_salt=None`` skips the request link — byte-compatible with the
    pre-engine per-read chain (fold leaf, fold pos).
    """
    out = {k: cim_lib.fold_seed(v, leaf_salt_) for k, v in seeds.items()}
    if req_salt is not None:
        out = {k: cim_lib.fold_seed(v, req_salt) for k, v in out.items()}
    return {k: cim_lib.fold_seed(v, pos) for k, v in out.items()}


# ---------------------------------------------------------------------------
# Dispatch: the single place that picks the execution route for a CIM matmul
# or row gather. models/lm.py and launch/serve.py call these instead of
# branching on mesh/dtype themselves.
# ---------------------------------------------------------------------------


@jax.jit
def _read_w_jit(store):
    """Jitted full decode of one store (cache warming / fault refresh)."""
    return cim_lib.read(store)[0]


def dispatch_linear(x, store, *, scalars=None, mesh=None, axis: str = "model",
                    dim: str = "j", with_info: bool = False, model=None):
    """Route ``x @ store`` by placement and dtype (module dispatch table).

    With a mesh carrying ``axis`` (default: the ambient mesh's "model" axis),
    the shard_map'd fused kernel runs one program per macro column group —
    degrading internally to GSPMD when the store cannot shard or tile.
    Otherwise a warmed decoded-row cache (``serving_params(row_cache=True)``)
    serves static reads as a full-f32-precision matmul against
    ``store.cache`` — on the CPU bitwise identical to the fused kernel's
    single-K-tile grids — and the
    single-device fused Pallas kernel handles everything else, itself falling
    back to the packed-jnp reference for ``per_weight`` / non-fp16 stores.
    ``scalars`` (``cim_read.ops.make_scalars``) turns on per-read dynamic
    injection and always bypasses the cache: per-request dynamic streams are
    keyed per read, never against a materialized image.
    """
    from repro.distributed import sharding as shlib
    from repro.kernels.cim_read import ops as cr_ops
    if mesh is None:
        mesh = shlib.get_mesh()
    if mesh is not None and axis in mesh.axis_names:
        return cr_ops.cim_linear_store_sharded(
            x, store, scalars=scalars, mesh=mesh, axis=axis, dim=dim,
            with_info=with_info, model=model)
    if scalars is None and store.cache is not None:
        b_shape = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        # full f32 passes, as the kernel's dot: at the TPU's default matmul
        # precision a single bf16 pass would round the fp16-exact weights
        out = jnp.matmul(x2, store.cache,
                         precision=jax.lax.Precision.HIGHEST)
        out = out.reshape(*b_shape, store.shape[1])
        if with_info:
            return out, {"used_kernel": False, "sharded": False,
                         "route": "cached"}
        return out
    return cr_ops.cim_linear_store(x, store, scalars=scalars,
                                   with_info=with_info, model=model)


def dispatch_read_rows(store, idx, *, seeds=None, thr_man=0, thr_meta=0,
                       model=None):
    """Row-gather route: decode-on-read off the packed image (no sharded
    variant — gathers are data-local; GSPMD partitions the jnp decode). A
    warmed decoded-row cache short-circuits static gathers; dynamic seeds
    bypass it (per-read streams are never served from a materialization)."""
    if seeds is None and store.cache is not None:
        return store.cache[idx]
    return cim_lib.read_rows(store, idx, seeds=seeds, thr_man=thr_man,
                             thr_meta=thr_meta, model=model)


# ---------------------------------------------------------------------------
# Training-time dynamic fault schedule (paper Fig. 7), policy-aware.
# ---------------------------------------------------------------------------


def training_fault_schedule(rel) -> Optional[Callable]:
    """Per-step weight corruption for dynamic-injection training, or None.

    With a uniform policy this is byte-for-byte the legacy schedule (same
    ``fault.inject_pytree`` key splits — training streams unchanged): the
    exponent/sign field sees the post-ECC residual rate of the active codec,
    mantissas the raw BER. With per-layer rules each leaf sees ITS rule's
    residual rate and BER scale.
    """
    from repro.core import fault as fault_lib
    if rel.mode != "cim" or rel.ber <= 0 or rel.inject != "dynamic":
        return None
    policy = getattr(rel, "policy", None)
    legacy_uniform = policy is None or (
        policy.uniform and policy.default.field == "full"
        and policy.default.ber_scale == 1.0)
    if legacy_uniform:
        exp_ber = rel.residual_exp_ber

        def corrupt(params, key):
            k1, k2 = jax.random.split(key)
            params = fault_lib.inject_pytree(
                k1, params, fault_lib.FaultModel(ber=exp_ber,
                                                 field="exponent_sign",
                                                 fmt=rel.fmt))
            params = fault_lib.inject_pytree(
                k2, params, fault_lib.FaultModel(ber=rel.ber, field="mantissa",
                                                 fmt=rel.fmt))
            return params

        return corrupt

    def residual(rule: PolicyRule) -> float:
        from repro.core.ecc import residual_ber_after_secded
        b = rel.ber * rule.ber_scale
        if rule.protect == "one4n":
            return residual_ber_after_secded(b, codec=rule.cim_cfg.codec)
        if rule.protect == "per_weight":
            return residual_ber_after_secded(b, codeword_bits=rule.cim_cfg
                                             .pw_code.n)
        return b

    def corrupt(params, key):
        k1, k2 = jax.random.split(key)
        leaves_wp, treedef = jax.tree_util.tree_flatten_with_path(params)
        keys1 = jax.random.split(k1, len(leaves_wp))
        keys2 = jax.random.split(k2, len(leaves_wp))
        out = []
        for ka, kb, (path, leaf) in zip(keys1, keys2, leaves_wp):
            rule = policy.rule_for(path_str(path))
            if rule.deploy and fault_lib._is_injectable(path, leaf):
                # honor the rule's cell-class restriction, matching
                # CIMDeployment.inject on the same policy
                if rule.field in ("full", "exponent_sign"):
                    leaf = fault_lib.inject(ka, leaf, residual(rule),
                                            "exponent_sign", rule.fmt)
                if rule.field in ("full", "mantissa"):
                    leaf = fault_lib.inject(kb, leaf,
                                            rel.ber * rule.ber_scale,
                                            "mantissa", rule.fmt)
            out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)

    return corrupt

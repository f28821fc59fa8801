"""Continuous-batching CIM serving engine with per-request fault streams.

The paper's threat model is soft errors striking the FP CIM macro *during
inference*; this engine is where that is demonstrated under realistic load.
It serves a stream of requests through a fixed decode batch of ``n_slots``
slots over the :class:`~repro.core.deployment.CIMDeployment` dispatch path:

* **admit** — a queued request (arrived, open-loop) takes a free slot; its
  prompt is chunk-prefilled (``chunk`` tokens per jitted call, ragged tail
  padded) into the slot's row of the batched slot states. Position-addressed
  kinds hide padding behind the causal mask until later writes overwrite it;
  fold kinds (rwkv/rec) mask padding out of the state fold itself. The final
  chunk's logits give the first token (TTFT is measured here).
* **decode** — one jitted :func:`repro.models.lm.decode_slots` step advances
  every active slot at its own position.
* **evict** — a slot that hits its request's ``max_new`` (or the cache
  ceiling ``max_len``) frees; the next queued request reuses it, lowest slot
  index first.

The engine is architecture-agnostic: it speaks only the slot-state protocol
(:class:`repro.models.lm.SlotStateSpec` and the ``init_slot_states`` /
``prefill_chunk`` / ``decode_slots`` / ``extract_state_chunk`` /
``inject_state_chunk`` operations), so KV-cache transformers, windowed
local attention, RWKV6, RecurrentGemma and expert-parallel MoE all serve
through the same admit/decode/evict loop. The only per-kind concessions are
shape clamps derived from the specs: ``chunk`` is clamped to the local
window when any block is ``window_bound`` (a ring buffer cannot absorb a
chunk larger than itself).

**Batch-invariance contract.** Every CIM read folds its dynamic-injection
seeds per (leaf salt, request salt, request-local position) — never per slot
index or engine step (:func:`repro.core.deployment.request_read_seeds`).
Prompt-prefill reads salt by prompt *content*
(:func:`repro.core.deployment.prefix_salt` of the tokens up through the
chunk); decode reads salt by request id
(:func:`repro.core.deployment.request_salt`). Decode math is row-independent
across slots for every kind (recurrent folds advance per-slot state and are
frozen while a slot is inactive), so a request's decoded tokens, logits and
injected-fault streams are bit-identical whether it is served alone or
continuously co-batched (``tests/test_engine.py`` asserts this for all five
kinds). The one contract boundary is capacity-coupled MoE dispatch: when
``moe.drop_free`` does not hold at the engine's shapes, co-batched tokens
can evict each other from expert capacity and the bitwise guarantee is
voided (fault-stream keying stays per-request). Drop-free configurations —
including every engine with ``max(n_slots, chunk) <= 8``, via the capacity
floor — retain the full guarantee; the engine warns only when actually
coupled (:func:`repro.models.lm.engine_capacity_coupled`).

**Prefix/state-cache reuse.** With a :class:`PrefixCache` attached,
admission walks the prompt's full leading chunks through a hash-consed
token-chunk trie: a hit injects the cached state chunk into the slot
(:func:`repro.models.lm.inject_state_chunk`) instead of re-running
``prefill_chunk``, and replays the chunk's ECC accounting from the same
(leaf, content-salt, position) counter-PRNG chain cold prefill would have
drawn — tokens, logits, fault streams and ECC counts stay bitwise identical
to a cold prefill, only TTFT drops. Cached units follow each block's spec:
KV rows for position-addressed kinds, the post-chunk state *snapshot* for
fold/window kinds — exact because those states are pure left folds over the
salted token prefix, and the engine always prefills at fixed ``chunk``
boundaries, so a cold recompute of the same prefix runs the same chunk
shapes and reproduces the snapshot bitwise. The final chunk always runs
cold (its logits emit the first token). Any image or runtime change must go
through :meth:`Engine.refresh_params`, which invalidates the trie (the
invalidation-on-inject contract: cached state embeds the faults of the
image it was prefilled against).

**Fleet hooks.** ``repro.launch.fleet`` runs N engines as data-parallel
replicas behind an SLO-aware router: :meth:`Engine.drain` hands back queued
and in-flight requests for re-admission elsewhere (re-serving from scratch
reproduces the same tokens — streams key on content/request/position, never
on the attempt), :attr:`Engine.depth` feeds the router's queue-depth
scoring, and :meth:`Engine.start` aligns the engine clock to the fleet's so
latency accounting shares one origin.

**Accounting.** Per request: queue wait, TTFT, decode seconds, tok/s, and
ECC activity — every CIM read is charged the macro's corrected/uncorrectable
codeword counts for the image that read observed (the static image's counts
per read, or the per-(request, position) dynamically-faulted image when a
``_cim`` runtime rides in params). Aggregate: tok/s over the decode loop and
per-slot occupancy.

**Tracing.** The engine writes its spans with ``jax.profiler``, so they sit
on the device trace's clock and record only while a trace is active (about
a microsecond each otherwise). Counters ride on the spans as arguments,
all known on the host when the span opens; the tree, with each span's
arguments:

* ``engine.step`` — one call of :meth:`Engine.step`;
* ``engine.admit`` — ``rid``, ``queue_ms`` (admission start minus submit
  time);
* ``engine.prefill`` — one chunk's dispatch: ``rid``, ``pos``, ``length``;
* ``engine.decode`` — the slot batch's dispatch: ``active``;
* ``engine.wait`` / ``engine.copy`` — waiting for the logits, then their
  device-to-host copy, where the host blocks anyway: ``of`` (``decode`` or
  ``prefill``);
* ``engine.charge_reads`` — ``rid``, ``pos``;
* ``engine.evict`` — ``rid``.

``LoadGen`` drives the engine open-loop: Poisson arrivals at ``rate`` req/s
(arrivals are wall-clock gated, independent of service) with uniform prompt
and generation length ranges.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core import cim as cim_lib
from repro.core import deployment as dep_lib
from repro.distributed import sharding as shlib
from repro.models import lm
from repro.training import steps as steps_lib


class EngineError(RuntimeError):
    """Non-finite logits or an inconsistent scheduler state."""


# one jitted (prefill_chunk, decode_slots, extract_state, inject_state) set per
# (ModelConfig, ambient mesh): every Engine instance over the same arch AND
# mesh shares the jit cache, so a fresh engine (e.g. a solo-request
# invariance replay, or every replica of a single-device fleet) costs zero
# recompiles at matched shapes. The mesh is part of the key because
# ``sharding.shard`` bakes the CONCRETE mesh (device ids included) into the
# trace — replicas on disjoint device blocks must not share executables
_STEP_CACHE: Dict[tuple, tuple] = {}


def _jitted_steps(cfg: ModelConfig) -> tuple:
    key = (cfg, shlib.get_mesh())
    if key not in _STEP_CACHE:
        _STEP_CACHE[key] = (
            jax.jit(steps_lib.make_prefill_chunk_step(cfg)),
            jax.jit(steps_lib.make_decode_slots_step(cfg)),
            jax.jit(steps_lib.make_extract_state_step(cfg), static_argnums=3),
            jax.jit(steps_lib.make_inject_state_step(cfg)))
    return _STEP_CACHE[key]


@dataclasses.dataclass
class Request:
    """One serving request: a prompt and a generation budget."""

    rid: int
    tokens: np.ndarray                 # [L] prompt token ids
    max_new: int = 16
    arrival: float = 0.0               # open-loop arrival time (s from start)

    def __post_init__(self):
        self.tokens = np.asarray(self.tokens, np.int32).reshape(-1)
        assert self.tokens.size >= 1, f"request {self.rid}: empty prompt"
        assert self.max_new >= 1, f"request {self.rid}: max_new must be >= 1"


@dataclasses.dataclass
class RequestResult:
    """Per-request serving record (the engine's JSON artifact rows)."""

    rid: int
    prompt_len: int
    tokens: List[int]                  # generated ids (greedy)
    finish: str                        # 'length' | 'max_len'
    queue_s: float                     # submit/arrival -> slot admission
    ttft_s: float                      # submit/arrival -> first token
    decode_s: float                    # wall time inside decode steps
    slot: int
    ecc: Dict[str, int]                # reads / corrected / uncorrectable
    finite: bool = True                # every served logit vector was finite
    logits: Optional[np.ndarray] = None   # [n_tokens, V] when collected
    replica: str = ""                  # fleet: name of the serving replica
    prefix_tokens: int = 0             # prompt tokens reused from the trie
    salt: int = 0                      # uint32 request salt (decode streams)
    ecc_window: List[Dict[str, int]] = dataclasses.field(
        default_factory=list)          # per decode-chunk ECC time series
    scrubs: int = 0                    # scrub events while this req was live

    def to_json(self) -> dict:
        tok_s = len(self.tokens) / self.decode_s if self.decode_s > 0 else 0.0
        return {"rid": self.rid, "prompt_len": self.prompt_len,
                "n_tokens": len(self.tokens), "finish": self.finish,
                "queue_s": self.queue_s, "ttft_s": self.ttft_s,
                "decode_s": self.decode_s, "tok_s": tok_s, "slot": self.slot,
                "ecc": {k: int(v) for k, v in self.ecc.items()},
                "ecc_window": [{k: int(v) for k, v in w.items()}
                               for w in self.ecc_window],
                "scrubs": self.scrubs,
                "finite": self.finite, "replica": self.replica,
                "prefix_hit": self.prefix_tokens > 0,
                "prefix_tokens": self.prefix_tokens, "salt": self.salt}


@dataclasses.dataclass
class _PrefixNode:
    """One full prefill chunk in the trie: (parent, chunk tokens) -> state."""

    nid: int
    key: tuple                         # (parent nid, chunk tokens bytes)
    salt: int                          # content salt its fault streams used
    state: object                      # state chunk (lm.extract_state_chunk)
    tokens: int                        # chunk length


class PrefixCache:
    """Hash-consed token-chunk trie of prefilled state chunks (per replica).

    A node is one FULL prefill chunk keyed by ``(parent node id, chunk token
    bytes)`` — the path from the root spells a prompt prefix in chunk steps,
    and identical chunks under the same parent share one node (hash-consing:
    inserting an existing chunk returns the existing node). Admission walks
    the trie over the prompt's full leading chunks; each hit injects the
    node's state chunk instead of recomputing it. Per-block cached units
    follow the :class:`repro.models.lm.SlotStateSpec`: KV rows for
    position-addressed kinds, post-chunk state snapshots for fold/window
    kinds (injection overwrites the slot's state, so the deepest hit wins).

    Reuse is exact: a node's state was prefilled under the content salt of
    its token prefix (``deployment.prefix_salt``), which is what a cold
    prefill of the same tokens would use — bitwise, including per-read
    dynamic injection; snapshot units are additionally exact because the
    engine prefills at fixed chunk boundaries, so the fold that produced a
    snapshot is re-run with identical chunk shapes on a cold recompute. The
    cache is therefore ONLY valid for the image/runtime it was filled
    against; :meth:`Engine.refresh_params` calls :meth:`invalidate` on any
    change (the invalidation-on-inject contract).

    Capacity is bounded at ``max_chunks`` nodes with least-recently-used
    eviction restricted to LEAF chunks — a parent is always at least as
    reachable as its children, so evicting interior nodes would orphan state
    a hot descendant still spells a path through.
    """

    def __init__(self, max_chunks: int = 256):
        assert max_chunks >= 1, max_chunks
        self.max_chunks = max_chunks
        self._nodes: Dict[tuple, _PrefixNode] = {}
        self._children: Dict[int, set] = {}
        self._lru: "OrderedDict[tuple, None]" = OrderedDict()
        self._next_id = 1
        self.hits = self.misses = self.inserts = self.evictions = 0
        self.invalidations = 0

    @staticmethod
    def _key(parent: Optional[_PrefixNode], tokens) -> tuple:
        pid = 0 if parent is None else parent.nid
        return (pid, np.asarray(tokens, np.int32).tobytes())

    def lookup(self, parent: Optional[_PrefixNode], tokens):
        node = self._nodes.get(self._key(parent, tokens))
        if node is None:
            self.misses += 1
            return None
        self.hits += 1
        self._lru.move_to_end(node.key)
        return node

    def insert(self, parent: Optional[_PrefixNode], tokens, state,
               salt) -> _PrefixNode:
        key = self._key(parent, tokens)
        node = self._nodes.get(key)
        if node is not None:            # hash-consed: one copy per chunk
            self._lru.move_to_end(key)
            return node
        node = _PrefixNode(nid=self._next_id, key=key, salt=int(salt),
                           state=state, tokens=int(np.asarray(tokens).size))
        self._next_id += 1
        self._nodes[key] = node
        self._children.setdefault(key[0], set()).add(key)
        self._lru[key] = None
        self.inserts += 1
        while len(self._nodes) > self.max_chunks and self._evict_leaf():
            pass
        return node

    def _evict_leaf(self) -> bool:
        for key in self._lru:           # oldest first
            if not self._children.get(self._nodes[key].nid):
                node = self._nodes.pop(key)
                self._children.get(key[0], set()).discard(key)
                self._children.pop(node.nid, None)
                del self._lru[key]
                self.evictions += 1
                return True
        return False

    def invalidate(self) -> None:
        """Drop every cached chunk (stale against a new image/runtime)."""
        self._nodes.clear()
        self._children.clear()
        self._lru.clear()
        self.invalidations += 1

    def __len__(self) -> int:
        return len(self._nodes)

    def stats(self) -> dict:
        return {"chunks": len(self._nodes),
                "tokens": sum(n.tokens for n in self._nodes.values()),
                "hits": self.hits, "misses": self.misses,
                "inserts": self.inserts, "evictions": self.evictions,
                "invalidations": self.invalidations}


@dataclasses.dataclass
class _Slot:
    rid: int
    prompt_len: int
    max_new: int
    submit_t: float
    admit_t: float
    req: Optional[Request] = None      # original request (fleet requeue)
    ttft_s: float = 0.0
    decode_s: float = 0.0
    finite: bool = True
    prefix_tokens: int = 0
    salt: int = 0
    tokens: List[int] = dataclasses.field(default_factory=list)
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)
    ecc: Dict[str, int] = dataclasses.field(
        default_factory=lambda: {"reads": 0, "corrected": 0,
                                 "uncorrectable": 0})
    ecc_window: List[Dict[str, int]] = dataclasses.field(default_factory=list)
    scrubs: int = 0


@dataclasses.dataclass
class LoadGen:
    """Synthetic open-loop load: Poisson arrivals, uniform length ranges.

    ``rate=float('inf')`` (the default) drops every arrival at t=0 — the
    closed "all at once" burst the tests and benches use; a finite rate
    draws exponential inter-arrival gaps (open loop: arrivals never wait for
    service).

    ``prefix_len > 0`` prepends one shared token prefix (drawn once from the
    same seed) to every prompt — the system-prompt workload that exercises
    the prefix cache. The schedule is a pure function of the config: the same
    ``LoadGen`` yields bit-identical requests whether they are then fed to
    one engine or fanned out across a fleet.
    """

    n_requests: int = 32
    rate: float = float("inf")         # requests / second
    prompt_lens: Tuple[int, int] = (8, 32)
    gen_lens: Tuple[int, int] = (4, 16)
    vocab_size: int = 256
    seed: int = 0
    prefix_len: int = 0                # shared leading tokens (0 = none)

    def requests(self) -> List[Request]:
        rng = np.random.default_rng(self.seed)
        if np.isinf(self.rate):
            arrivals = np.zeros(self.n_requests)
        else:
            arrivals = np.cumsum(rng.exponential(1.0 / self.rate,
                                                 self.n_requests))
        # drawn before the per-request loop so prefix_len=0 reproduces the
        # historical schedules exactly (no extra rng consumption)
        prefix = (rng.integers(0, self.vocab_size, self.prefix_len)
                  if self.prefix_len > 0 else None)
        out = []
        for i in range(self.n_requests):
            plen = int(rng.integers(self.prompt_lens[0],
                                    self.prompt_lens[1] + 1))
            gen = int(rng.integers(self.gen_lens[0], self.gen_lens[1] + 1))
            toks = rng.integers(0, self.vocab_size, plen)
            if prefix is not None:
                toks = np.concatenate([prefix, toks])
            out.append(Request(rid=i, tokens=toks, max_new=gen,
                               arrival=float(arrivals[i])))
        return out

    def max_len(self) -> int:
        return self.prefix_len + self.prompt_lens[1] + self.gen_lens[1] + 1


class Engine:
    """Slot-based continuous-batching serving over a params pytree.

    ``params`` is whatever :meth:`CIMDeployment.serving_params` produced —
    packed stores (fused), decoded fp16 (hbm), or plain weights, plus the
    optional ``_cim`` dynamic-injection runtime. Four jitted programs total:
    one full-chunk prefill, one ragged-chunk prefill per distinct tail
    length, one slot decode, and the state extract/inject pair the prefix
    cache rides on.

    ``prefix_cache`` attaches a :class:`PrefixCache` (pass your own, or
    ``True`` for a default-sized one). ``replica`` names this engine in
    fleet artifacts (``RequestResult.replica``).
    """

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 4,
                 max_len: int = 64, chunk: int = 16,
                 collect_logits: bool = False, ecc_accounting: bool = True,
                 check_finite: bool = True, prefix_cache=None,
                 replica: str = ""):
        specs = lm.check_engine_kinds(cfg)
        assert n_slots >= 1 and chunk >= 1 and max_len >= 2, \
            (n_slots, chunk, max_len)
        self.cfg = cfg
        self.params = params
        self.replica = replica
        # a chunk never writes past the cache ceiling (an overflowing padded
        # dynamic_update_slice would clamp backwards over real prompt rows);
        # window-bound kinds additionally cap the chunk at the ring size (a
        # W-slot ring cannot absorb more than W new tokens in one write)
        chunk = min(chunk, max_len)
        if any(s.window_bound for s in specs):
            chunk = min(chunk, cfg.local_window)
        self.n_slots, self.max_len, self.chunk = n_slots, max_len, chunk
        # capacity-coupled MoE dispatch at these shapes voids the bitwise
        # solo-vs-cobatched guarantee (moe.drop_free documents the boundary)
        self.capacity_coupled = lm.engine_capacity_coupled(
            cfg, max(n_slots, self.chunk))
        if self.capacity_coupled:
            warnings.warn(
                "engine: MoE dispatch is capacity-coupled at these shapes "
                f"(n_slots={n_slots}, chunk={self.chunk}): co-batched tokens "
                "may contend for expert capacity, voiding the bitwise "
                "solo-vs-cobatched guarantee (fault streams stay "
                "per-request). Raise capacity_factor or shrink the batch "
                "until moe.drop_free holds to restore it.")
        self.collect_logits = collect_logits
        self.check_finite = check_finite
        self._prefill, self._decode, self._extract, self._inject = \
            _jitted_steps(cfg)
        self.prefix_cache: Optional[PrefixCache] = \
            PrefixCache() if prefix_cache is True else prefix_cache
        self.caches = lm.init_slot_states(cfg, n_slots, max_len)
        self.caches["pos"] = jnp.zeros((n_slots,), jnp.int32)
        self.slots: List[Optional[_Slot]] = [None] * n_slots
        self.queue: deque[Tuple[Request, float]] = deque()
        self._tokens = np.zeros((n_slots, 1), np.int32)
        self._salts = np.zeros(n_slots, np.uint32)
        self.results: Dict[int, RequestResult] = {}
        self.steps = 0
        self.idle_steps = 0
        self.requeues = 0
        self._decode_wall = 0.0
        self._decoded_tokens = 0
        self._ecc_accounting = ecc_accounting
        self._runtime = params.get("_cim") if isinstance(params, dict) \
            else None
        # per-store cumulative ECC charges (path -> counters): the signal a
        # ScrubPolicy thresholds on. Survives refresh_params — scrubbing
        # resets it per store via launch.scrub, not here.
        self.store_ecc: Dict[str, Dict[str, int]] = {}
        self.scrub_events: List[dict] = []
        self._ecc_fns = self._build_ecc_fns() if ecc_accounting else []

    # ------------------------------------------------------------ ECC

    def _build_ecc_fns(self):
        """One per-read ECC accountant per deployed store leaf, called with
        host values: ``fn(salt, pos) -> (corrected, uncorrectable)``.

        Static image: the macro's corrected/uncorrectable counts are a
        constant of the image — computed once, charged per read as plain
        Python, with no device work. Dynamic runtime (the only accountant
        that runs on the device): a jitted fn, fed ``np.uint32`` /
        ``np.int32`` scalars, re-derives the (request, position) flip streams
        (the exact chain the model's reads use) and counts the ECC events of
        that read's faulted image. That re-derivation decodes the FULL
        codeword planes per active slot per step (the serving read itself
        never surfaces ECC status), so dynamic accounting costs the same
        order as the decode it observes — fine for reduced-arch soaks, and
        exactly what ``ecc_accounting=False`` (``--no-ecc-accounting``)
        switches off for throughput measurement (``engine_bench.py`` does).
        """
        fns = []
        flat = jax.tree_util.tree_flatten_with_path(
            self.params, is_leaf=cim_lib._is_store)[0]
        rt = self._runtime
        model = rt.get("model") if rt is not None else None
        if model is not None and model.kind == "drift":
            # reads absorb drift's time scaling into the thresholds (keyed on
            # the request-local pos); the model handed downstream is tick-0
            model0 = dataclasses.replace(model, tick=0)
        else:
            model0 = model
        for path, leafv in flat:
            if not cim_lib._is_store(leafv):
                continue
            pstr = dep_lib.path_str(path)
            salt = dep_lib.leaf_salt(pstr)
            self.store_ecc.setdefault(
                pstr, {"reads": 0, "corrected": 0, "uncorrectable": 0})
            if rt is None:
                st = cim_lib.store_stats(leafv)
                const = (int(st["corrected"]), int(st["uncorrectable"]))
                fns.append((pstr, lambda req_salt, pos, c=const: c))
            else:
                from repro.core import faultmodels as fm_lib

                def dyn(req_salt, pos, store=leafv, leaf_salt=salt):
                    seeds = dep_lib.request_read_seeds(
                        rt["seeds"], leaf_salt, req_salt, pos)
                    tm = fm_lib.compiled_threshold(model, rt["thr_man"],
                                                   tick=pos)
                    tt = fm_lib.compiled_threshold(model, rt["thr_meta"],
                                                   tick=pos)
                    faulted = cim_lib.inject_with_seeds(store, seeds, tm, tt,
                                                        model=model0)
                    st = cim_lib.store_stats(faulted)
                    return jnp.stack([st["corrected"], st["uncorrectable"]])
                jfn = jax.jit(dyn)
                fns.append((pstr, lambda req_salt, pos, f=jfn: tuple(
                    int(v) for v in np.asarray(
                        f(np.uint32(req_salt), np.int32(pos))))))
        return fns

    def _charge_reads(self, slot: _Slot, salt, pos: int) -> None:
        """Charge one CIM read (all deployed macros) at read index ``pos``.

        ``salt`` and ``pos`` are host values and go to each store's
        accountant as they are: a static image's charge is host arithmetic
        alone, and only a dynamic image's accountant runs on the device.
        Besides the request's cumulative counters, every charge lands in the
        request's ``ecc_window`` time series (one row per decode chunk, the
        scrub-decision observable) and the engine's per-store ``store_ecc``
        totals (the ScrubPolicy threshold signal)."""
        if not self._ecc_fns:
            return
        with TraceAnnotation("engine.charge_reads", rid=slot.rid,
                             pos=int(pos)):
            slot.ecc["reads"] += 1
            corr = unc = 0
            for pstr, fn in self._ecc_fns:
                c, u = fn(salt, pos)
                corr += c
                unc += u
                store = self.store_ecc[pstr]
                store["reads"] += 1
                store["corrected"] += c
                store["uncorrectable"] += u
            slot.ecc["corrected"] += corr
            slot.ecc["uncorrectable"] += unc
            slot.ecc_window.append({"pos": int(pos), "reads": 1,
                                    "corrected": corr, "uncorrectable": unc})

    # ------------------------------------------------------------ scheduling

    def submit(self, req: Request, now: Optional[float] = None) -> None:
        self.queue.append((req, now if now is not None else req.arrival))

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    @property
    def active(self) -> np.ndarray:
        return np.asarray([s is not None for s in self.slots])

    def _admit(self, req: Request, slot_idx: int, submit_t: float) -> None:
        """Chunk-prefill the request's prompt into ``slot_idx`` and emit its
        first token, reusing trie-cached state chunks where they match.

        Prefill fault streams key on prompt *content*
        (:func:`repro.core.deployment.prefix_salt` of the tokens up through
        the chunk), so a cached chunk's state — and its replayed ECC charges
        — are bitwise what a cold prefill of the same tokens would produce.
        The final chunk always runs cold: its logits emit the first token.
        """
        plen = req.tokens.size
        if plen + req.max_new > self.max_len:
            raise EngineError(
                f"request {req.rid}: prompt {plen} + max_new {req.max_new} "
                f"exceeds the engine's max_len {self.max_len}")
        # admit_t comes from the wall clock, never the admission gate `now`
        # (a closed-loop run gates with now=inf — that must not leak into
        # queue_s or the JSON artifact)
        admit_t = self._clock()
        with TraceAnnotation("engine.admit", rid=req.rid,
                             queue_ms=1e3 * (admit_t - submit_t)):
            rsalt = np.uint32(dep_lib.request_salt(req.rid))
            slot = _Slot(rid=req.rid, prompt_len=plen, max_new=req.max_new,
                         submit_t=submit_t, admit_t=admit_t, req=req,
                         salt=int(rsalt))
            # walk the trie over the prompt's full LEADING chunks (never the
            # final one — its logits are the first token, so it must run);
            # `prefill_chunk` masks off the explicit pos argument and the
            # always-cold final chunk leaves caches['pos'][slot] = plen, so
            # injection only has to land the state chunk (KV rows, or the
            # post-chunk snapshot for fold/window kinds — deepest hit wins)
            starts = list(range(0, plen, self.chunk))
            node = None
            pos = 0
            if self.prefix_cache is not None:
                for c0 in starts[:-1]:
                    seg = req.tokens[c0:c0 + self.chunk]
                    hit = self.prefix_cache.lookup(node, seg)
                    if hit is None:
                        break
                    self.caches = self._inject(
                        self.caches, jnp.int32(slot_idx), jnp.int32(c0),
                        hit.state)
                    # replay the ECC accounting of the read this chunk's cold
                    # prefill would have issued — same salt, same read index
                    self._charge_reads(slot, np.uint32(hit.salt), c0)
                    node = hit
                    pos = c0 + self.chunk
            slot.prefix_tokens = pos
            logits = None
            for c0 in range(pos, plen, self.chunk):
                seg = req.tokens[c0:c0 + self.chunk]
                length = seg.size
                csalt = np.uint32(
                    dep_lib.prefix_salt(req.tokens[:c0 + length]))
                # the ragged tail pads only to what still fits under max_len
                # (padding row writes must not clamp back over prompt rows);
                # pad length never enters the fault-stream chain
                pad_to = min(self.chunk, self.max_len - c0)
                padded = np.pad(seg, (0, pad_to - length))
                with TraceAnnotation("engine.prefill", rid=req.rid, pos=c0,
                                     length=length):
                    logits, self.caches = self._prefill(
                        self.params, self.caches, jnp.asarray(padded),
                        jnp.int32(slot_idx), jnp.int32(c0), jnp.int32(length),
                        jnp.uint32(csalt))
                self._charge_reads(slot, csalt, c0)
                if self.prefix_cache is not None and length == self.chunk:
                    state = self._extract(self.caches, jnp.int32(slot_idx),
                                          jnp.int32(c0), self.chunk)
                    node = self.prefix_cache.insert(node, seg, state, csalt)
            logits = self._fetch(logits, "prefill")
            self._check(logits, slot)
            tok = int(np.argmax(logits))
            slot.tokens.append(tok)
            if self.collect_logits:
                slot.logits.append(logits)
            slot.ttft_s = self._clock() - submit_t
            self.slots[slot_idx] = slot
            self._tokens[slot_idx, 0] = tok
            self._salts[slot_idx] = rsalt

    def _evict(self, slot_idx: int, finish: str) -> None:
        slot = self.slots[slot_idx]
        with TraceAnnotation("engine.evict", rid=slot.rid):
            res = RequestResult(
                rid=slot.rid, prompt_len=slot.prompt_len, tokens=slot.tokens,
                finish=finish, queue_s=slot.admit_t - slot.submit_t,
                ttft_s=slot.ttft_s, decode_s=slot.decode_s, slot=slot_idx,
                ecc=slot.ecc, finite=slot.finite,
                logits=np.stack(slot.logits) if slot.logits else None,
                replica=self.replica, prefix_tokens=slot.prefix_tokens,
                salt=slot.salt, ecc_window=slot.ecc_window, scrubs=slot.scrubs)
            self.results[slot.rid] = res
            self.slots[slot_idx] = None
            # reset the slot's position so the next admission prefills from 0;
            # stale KV/ring rows stay causally masked until overwritten, and
            # prefill_chunk zeroes fold states (rwkv/rec) at pos == 0
            self.caches["pos"] = self.caches["pos"].at[slot_idx].set(0)

    def _check(self, logits: np.ndarray, slot: _Slot) -> None:
        """Record the slot's actual finiteness verdict (the JSON artifact
        reports it) and, when ``check_finite``, fail fast on violation."""
        if not np.isfinite(logits).all():
            slot.finite = False
            if self.check_finite:
                raise EngineError(
                    f"non-finite logits serving request {slot.rid}")

    @staticmethod
    def _fetch(logits, of: str) -> np.ndarray:
        """Wait for the logits, then copy them to the host: where the host
        blocks anyway, split into the wait for the program and the copy."""
        with TraceAnnotation("engine.wait", of=of):
            jax.block_until_ready(logits)
        with TraceAnnotation("engine.copy", of=of):
            return np.asarray(logits)

    def _clock(self) -> float:
        return time.perf_counter() - self._t0

    # ------------------------------------------------------------ fleet hooks

    @property
    def depth(self) -> int:
        """Queued + in-flight request count (the router's load signal)."""
        return len(self.queue) + int(self.active.sum())

    @property
    def busy(self) -> bool:
        return bool(self.queue) or bool(self.active.any())

    def start(self, t0: Optional[float] = None) -> None:
        """Pin the engine clock origin (fleet replicas share the router's
        ``t0`` so queue/TTFT accounting has one time base)."""
        self._t0 = time.perf_counter() if t0 is None else t0

    def drain(self) -> List[Request]:
        """Abandon all work and hand the requests back, arrival order.

        In-flight requests are dropped mid-generation and returned whole —
        re-serving one from scratch reproduces the exact tokens, logits and
        fault streams of an uninterrupted run, because every stream keys on
        content/request/position, never on the attempt or the slot. Queued
        requests ride along. Slots and cache positions reset; the prefix
        trie survives (its state is a pure function of the image, not of
        which requests ran).
        """
        back: List[Request] = []
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            assert slot.req is not None, slot.rid
            back.append(slot.req)
            self.slots[i] = None
            self.caches["pos"] = self.caches["pos"].at[i].set(0)
        back.extend(req for req, _ in self.queue)
        self.queue.clear()
        self.requeues += len(back)
        back.sort(key=lambda r: (r.arrival, r.rid))
        return back

    def refresh_params(self, params, *, force: bool = False) -> None:
        """Swap in a new deployed image/runtime (engine must be idle).

        The invalidation-on-inject contract: cached prefix state embeds the
        faults of the image it was prefilled against, so ANY params change
        drops the trie before the next admission can hit it.

        ``force=True`` swaps while requests are in flight — the online
        scrubbing/aging path. In-flight slot state stays (it embeds the
        faults of the image it was computed against — exactly the physics:
        old reads saw the old cells); subsequent reads see the new image.
        """
        if self.busy and not force:
            raise EngineError("refresh_params on a busy engine: drain first")
        self.params = params
        self._runtime = params.get("_cim") if isinstance(params, dict) \
            else None
        self._ecc_fns = self._build_ecc_fns() if self._ecc_accounting else []
        if self.prefix_cache is not None:
            self.prefix_cache.invalidate()

    def record_scrub(self, event: dict) -> None:
        """Log one scrub event (``launch.scrub`` calls this) and mark every
        in-flight request as having lived through it; per-store cumulative
        counters of the scrubbed stores reset (damage cleared)."""
        self.scrub_events.append(dict(event))
        for s in self.slots:
            if s is not None:
                s.scrubs += 1
        for pstr in event.get("paths", ()):
            if pstr in self.store_ecc:
                self.store_ecc[pstr] = {"reads": 0, "corrected": 0,
                                        "uncorrectable": 0}

    # ------------------------------------------------------------ stepping

    def step(self, now: Optional[float] = None) -> dict:
        """Admit arrived requests into free slots, then advance every active
        slot by one token. Returns an event dict (admitted/decoded/evicted
        rids, ``idle`` when there was nothing to do)."""
        with TraceAnnotation("engine.step"):
            return self._step(now)

    def _step(self, now: Optional[float]) -> dict:
        if not hasattr(self, "_t0"):
            self._t0 = time.perf_counter()
        if now is None:
            now = self._clock()
        admitted, evicted = [], []
        while self.queue and self.free_slots():
            req, submit_t = self.queue[0]
            if submit_t > now:
                break
            self.queue.popleft()
            idx = self.free_slots()[0]
            self._admit(req, idx, submit_t)
            admitted.append(req.rid)
            # a 1-token request is done at TTFT
            if len(self.slots[idx].tokens) >= req.max_new:
                self._evict(idx, "length")
                evicted.append(req.rid)

        active = self.active
        if not active.any():
            self.idle_steps += 1
            return {"idle": True, "admitted": admitted, "evicted": evicted,
                    "decoded": []}

        t0 = time.perf_counter()
        n_active = int(active.sum())
        with TraceAnnotation("engine.decode", active=n_active):
            logits, self.caches = self._decode(
                self.params, self.caches, jnp.asarray(self._tokens),
                jnp.asarray(active), jnp.asarray(self._salts))
        logits = self._fetch(logits, "decode")
        dt = time.perf_counter() - t0
        self.steps += 1
        decoded = []
        for i in np.flatnonzero(active):
            slot = self.slots[i]
            self._check(logits[i], slot)
            tok = int(np.argmax(logits[i]))
            slot.tokens.append(tok)
            if self.collect_logits:
                slot.logits.append(logits[i])
            slot.decode_s += dt / n_active
            # the read index this decode step consumed: the slot's pre-step
            # position (prefill left it at prompt_len; each decode adds 1)
            self._charge_reads(slot, self._salts[i],
                               slot.prompt_len + len(slot.tokens) - 2)
            self._tokens[i, 0] = tok
            decoded.append(slot.rid)
            self._decoded_tokens += 1
        self._decode_wall += dt
        for i in np.flatnonzero(active):
            slot = self.slots[i]
            done = len(slot.tokens) >= slot.max_new
            full = slot.prompt_len + len(slot.tokens) >= self.max_len
            if done or full:
                self._evict(int(i), "length" if done else "max_len")
                evicted.append(slot.rid)
        return {"idle": False, "admitted": admitted, "decoded": decoded,
                "evicted": evicted}

    def run(self, requests, *, open_loop: bool = False, on_step=None
            ) -> Tuple[Dict[int, RequestResult], dict]:
        """Serve ``requests`` to completion -> (results by rid, aggregate).

        ``open_loop=True`` gates admissions on each request's wall-clock
        ``arrival`` offset (the Poisson load); otherwise everything is
        admissible immediately and ``arrival`` only sets the queue order.

        ``on_step(engine, event)`` runs after every engine step — the hook
        the online scrub controller (``launch.scrub.ScrubController``) and
        aging schedules interleave with request slots.
        """
        self._t0 = time.perf_counter()
        for req in sorted(requests, key=lambda r: (r.arrival, r.rid)):
            self.submit(req, now=req.arrival if open_loop else 0.0)
        while self.queue or self.active.any():
            ev = self.step(now=None if open_loop else float("inf"))
            if on_step is not None:
                on_step(self, ev)
            if ev["idle"] and self.queue:
                # open loop: nothing active and the next arrival is in the
                # future — sleep to it instead of spinning
                nxt = self.queue[0][1]
                wait = nxt - self._clock()
                if wait > 0:
                    time.sleep(min(wait, 0.05))
        return self.results, self.aggregate()

    # ------------------------------------------------------------ reporting

    def aggregate(self) -> dict:
        res = list(self.results.values())
        ttfts = np.asarray([r.ttft_s for r in res]) if res else np.zeros(1)
        total_tok = sum(len(r.tokens) for r in res)
        wall = self._clock() if hasattr(self, "_t0") else 0.0
        return {
            "replica": self.replica,
            "n_requests": len(res),
            "n_slots": self.n_slots,
            "total_tokens": total_tok,
            "decode_steps": self.steps,
            "idle_steps": self.idle_steps,
            "wall_s": wall,
            "decode_wall_s": self._decode_wall,
            "decode_tok_s": (self._decoded_tokens / self._decode_wall
                             if self._decode_wall > 0 else 0.0),
            "tok_s": total_tok / wall if wall > 0 else 0.0,
            "ttft_s_mean": float(ttfts.mean()),
            "ttft_s_p95": float(np.percentile(ttfts, 95)),
            "slot_occupancy": (self._decoded_tokens
                               / max(self.steps * self.n_slots, 1)),
            "requeues": self.requeues,
            "prefix_hits": sum(1 for r in res if r.prefix_tokens > 0),
            "prefix_tokens": sum(r.prefix_tokens for r in res),
            "prefix_cache": (self.prefix_cache.stats()
                             if self.prefix_cache is not None else None),
            "ecc": {k: int(sum(r.ecc[k] for r in res))
                    for k in ("reads", "corrected", "uncorrectable")},
            "store_ecc": {p: dict(v) for p, v in self.store_ecc.items()},
            "scrub": self._scrub_summary(),
        }

    def _scrub_summary(self) -> dict:
        ev = self.scrub_events
        return {
            "events": len(ev),
            "rows_reencoded": int(sum(e.get("rows", 0) for e in ev)),
            "corrected_cleared": int(sum(e.get("corrected_cleared", 0)
                                         for e in ev)),
            "uncorrectable_cleared": int(sum(e.get("uncorrectable_cleared", 0)
                                             for e in ev)),
            "wall_s": float(sum(e.get("wall_s", 0.0) for e in ev)),
        }

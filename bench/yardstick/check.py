"""The comparison that decides ``correct``: served requests against the
plain reference, which rebuilds the deployed image from the source weights
and the seed (``bench/reference``) and imports nothing of the program.

For each sampled finished request, the reference runs once over the prompt
followed by the served tokens. Every embedding row and every unembedding
read uses the faulted image that read saw: static serving reads one image
per matrix; dynamic serving reads a fresh image per (request salt, read
position), the prompt chunks salted by their content. Two numbers:

* ``gap``: the widest margin by which a served token's reference logit lies
  below the reference's best logit at that position, as a share of the
  two logits' magnitude scale ``(|h| @ |W|)`` (greedy serving gives 0 up
  to rounding; see ``_margin``);
* ``ecc_mismatch``: sampled requests whose charged ECC counts (reads,
  corrected, uncorrectable codewords) differ from the reference's.

``control`` reads the same prompts and tokens with the reference put in
the program's place one precision step below what the configuration
states: the blocks' matmuls, stated as one bfloat16 pass, with float8
(e4m3) operands, and the unembed, stated at HIGHEST, at HIGH (three bf16
passes). At each position it reads the margin of the token that control
puts first.
"""
from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import faults as F

CONTROL_DOT = jnp.float8_e4m3fn


def _block_rows(k: int, j: int) -> int:
    """Rows per block of an image pass: about 128k codewords of one segment
    per block (8 rows x 16 columns each), and a divisor of ``k``."""
    want = max(8, min(k, 8 * max(1, 131072 // (j // F.ROW))))
    b = 8
    while b * 2 <= want and k % (b * 2) == 0:
        b *= 2
    return b


def sample(results: dict, n: int, seed: int) -> list:
    """``n`` finished requests drawn from the seed, the longest served one
    always among them."""
    rids = sorted(results)
    if not rids:
        return []
    longest = max(rids, key=lambda r: (len(results[r]["tokens"]), -r))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, 9])
    pick = list(rng.permutation(rest)[:max(n - 1, 0)])
    return [longest] + [int(r) for r in pick]


class Reference:
    """The reference's view of one deployment: source weights, the image
    fields it derives from them, and the seeds of its soft errors."""

    def __init__(self, conf: dict, params, dep_key, inject: str, chunk: int,
                 pad_len: int):
        self.model = conf["model"]
        self.pad_len = pad_len
        self.thr = F.threshold(conf["deployment"]["ber"])
        mod = importlib.import_module(f"reference.{conf['reference']}")
        self.hidden = jax.jit(mod.hidden, static_argnums=(1, 3))
        self.params = params
        self.chunk = chunk
        self.dynamic = inject == "dynamic"
        self.fields = {m: jax.jit(F.align)(params[m])
                       for m in ("embed", "unembed")}
        self.shape = {m: tuple(params[m].shape) for m in ("embed", "unembed")}
        if self.dynamic:
            self.seeds = F.plane_seeds(jax.random.fold_in(dep_key, 99))
            return
        # static image: one key per deployed matrix, split over the
        # checkpoint's leaves in tree order
        paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]]
        keys = jax.random.split(dep_key, len(paths))
        self.static = {}
        for m in ("embed", "unembed"):
            seeds = F.plane_seeds(keys[paths.index(f"['{m}']")])
            c, u = _ecc(seeds["cw"], self.thr, self.shape[m])
            self.static[m] = (seeds, (int(c), int(u)))
        self.unembed_img, _, _ = _image(self.fields["unembed"],
                                        self.static["unembed"][0], self.thr)

    # ---------------------------------------------------------- reads

    def _reads(self, prompt, served, rid):
        """[(salt, pos, rows fed at this read, logit position or None)]
        in read order."""
        plen = len(prompt)
        out = []
        starts = list(range(0, plen, self.chunk))
        for c0 in starts:
            seg = prompt[c0:c0 + self.chunk]
            salt = F.prefix_salt(prompt[:c0 + len(seg)])
            last = plen - 1 if c0 == starts[-1] else None
            out.append((salt, c0, np.arange(c0, c0 + len(seg)), last))
        rsalt = int(F.request_salt(rid))
        for i in range(len(served) - 1):
            out.append((rsalt, plen + i, np.asarray([plen + i]), plen + i))
        return out

    def request(self, prompt, served, rid, control=False):
        """-> (gap, control reading or None, ECC counts)."""
        prompt = np.asarray(prompt, np.int32)
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        assert len(seq) <= self.pad_len, (len(seq), self.pad_len)
        reads = self._reads(prompt, served, rid)
        ecc = {"reads": len(reads), "corrected": 0, "uncorrectable": 0}
        if self.dynamic:
            x = np.zeros((self.pad_len, self.model["d_model"]), np.float32)
            for salt, pos, idx, lp in reads:
                toks = np.zeros(self.chunk, np.int32)
                toks[:len(idx)] = seq[idx]
                rows, counts = _dyn_embed(
                    self.fields["embed"], self.seeds, jnp.uint32(salt),
                    jnp.int32(pos), self.thr, jnp.asarray(toks),
                    shape_e=self.shape["embed"], shape_u=self.shape["unembed"],
                    unembed=lp is None)
                x[idx] = np.asarray(rows)[:len(idx)]
                self._charge(ecc, [int(v) for v in counts])
            x = jnp.asarray(x)
        else:
            padded = np.zeros(self.pad_len, np.int32)
            padded[:len(seq)] = seq
            x = _rows(self.fields["embed"], jnp.asarray(padded),
                      self.static["embed"][0], self.thr)
            c = sum(self.static[m][1][0] for m in ("embed", "unembed"))
            u = sum(self.static[m][1][1] for m in ("embed", "unembed"))
            ecc.update(corrected=c * len(reads), uncorrectable=u * len(reads))
        model = _frozen(self.model)
        h = self.hidden(self.params, model, x, jnp.float32)
        h_lo = self.hidden(self.params, model, x, CONTROL_DOT) \
            if control else h
        gap = ctl = self.gap_abs = 0.0
        k = 0
        for salt, pos, _, lp in reads:
            if lp is None:
                continue
            tok = jnp.int32(int(served[k]))
            if self.dynamic:
                out = _dyn_logits(self.fields["unembed"], self.seeds,
                                  jnp.uint32(salt), jnp.int32(pos), self.thr,
                                  h[lp], h_lo[lp], tok)
                out = [float(v) for v in out]
                self._charge(ecc, [0, 0] + [int(v) for v in out[4:]])
            else:
                w = self.unembed_img
                out = [float(v) for v in _margin(h[lp], w, tok,
                                                 _pick_lo(h_lo[lp], w))]
            g, c, a, finite = out[:4]
            if not finite:
                g = c = a = float("inf")
            gap, ctl = max(gap, g), max(ctl, c)
            self.gap_abs = max(self.gap_abs, a)
            k += 1
        assert k == len(served), (k, len(served))
        return gap, (ctl if control else None), ecc

    @staticmethod
    def _charge(ecc, counts):
        ecc["corrected"] += counts[0] + counts[2]
        ecc["uncorrectable"] += counts[1] + counts[3]


def _ecc(seed_cw, thr, shape):
    k, j = shape
    return F.ecc_counts(seed_cw, thr, k=k, j=j, block_rows=_block_rows(k, j))


def _image(fields, seeds, thr):
    k, j = fields[2].shape
    return F.image(fields, seeds, thr, block_rows=_block_rows(k, j))


_rows = jax.jit(F.image_rows)


@functools.partial(jax.jit, static_argnames=("shape_e", "shape_u",
                                             "unembed"))
def _dyn_embed(fields_e, seeds, salt, pos, thr, toks, *, shape_e, shape_u,
               unembed):
    """One dynamic read's embedding rows and ECC counts: the embed store's,
    and the unembed store's unless its image is built at this read."""
    se = F.read_seeds(seeds, "embed", salt, pos)
    rows = F.image_rows(fields_e, toks, se, thr)
    ce, ue = _ecc(se["cw"], thr, shape_e)
    cu = uu = jnp.zeros((), jnp.int32)
    if unembed:
        su = F.read_seeds(seeds, "unembed", salt, pos)
        cu, uu = _ecc(su["cw"], thr, shape_u)
    return rows, jnp.stack([ce, ue, cu, uu])


@jax.jit
def _dyn_logits(fields_u, seeds, salt, pos, thr, h, h_lo, served):
    """One dynamic read's unembed image, the margins at this position and
    the image's ECC counts."""
    su = F.read_seeds(seeds, "unembed", salt, pos)
    w, c, u = _image(fields_u, su, thr)
    m = _margin(h, w, served, _pick_lo(h_lo, w))
    return jnp.concatenate([m, jnp.stack([c, u]).astype(jnp.float32)])


class _frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@jax.jit
def _logits(h, w):
    return jnp.matmul(h[None], w, precision=jax.lax.Precision.HIGHEST)[0]


@jax.jit
def _margin(h, w, served, pick):
    """Reference deficit of token ``served`` and of ``pick``, each over the
    magnitude scale of the two logits compared, ``(|h| @ |W|)``: a
    relative error e in every product moves logit j by at most
    e * (|h| @ |W|)_j, whatever the weights' size. Columns that a fault
    made 2^16 times larger keep the measure in proportion."""
    ref = _logits(h, w)
    scale = _logits(jnp.abs(h), jnp.abs(w))
    best = jnp.argmax(ref)

    def rel(j):
        return (ref[best] - ref[j]) / jnp.maximum(
            jnp.maximum(scale[best], scale[j]), 1e-30)
    finite = jnp.all(jnp.isfinite(ref)).astype(jnp.float32)
    return jnp.stack([rel(served), rel(pick), ref[best] - ref[served],
                      finite]).astype(jnp.float32)


@jax.jit
def _pick_lo(h_lo, w):
    return jnp.argmax(jnp.matmul(h_lo[None], w,
                                 precision=jax.lax.Precision.HIGH)[0])


def compare(ref: Reference, finished: dict, rids, control=False) -> dict:
    """``finished``: rid -> {"prompt", "tokens", "ecc"}. Returns the numbers
    compared, over the sampled ``rids``."""
    gap, ctl, mism, served, gap_abs = 0.0, 0.0, 0, 0, 0.0
    for rid in rids:
        r = finished[rid]
        g, c, ecc = ref.request(r["prompt"], r["tokens"], rid, control)
        gap = max(gap, g)
        gap_abs = max(gap_abs, ref.gap_abs)
        if control:
            ctl = max(ctl, c)
        served += len(r["tokens"])
        got = {k: int(r["ecc"][k]) for k in ("reads", "corrected",
                                              "uncorrectable")}
        if got != ecc:
            mism += 1
    out = {"gap": gap if math.isfinite(gap) else float("inf"),
           "ecc_mismatch": mism, "requests": len(rids), "tokens": served,
           "gap_abs": gap_abs}
    if control:
        out["control_gap"] = ctl
    return out

"""GAT (Velickovic et al., ICLR 2018, arXiv:1710.10903), as published and
as Fograph's Table I lists it. A layer of K heads of width F' has
``w`` [F_in, K F'] (head k's columns k F' .. (k + 1) F'), ``att_src`` and
``att_dst`` [K, F'], and, where it has a skip, ``w_res`` [F_in, F_out].
Per head k, over u in N(v) and v itself (a self loop):

    z_k   = h W_k
    e_vu  = LeakyReLU_0.2(a_dst,k . z_k[v] + a_src,k . z_k[u])
    a_vu  = softmax over u of e_vu, per receiver v
    o_k   = sum_u a_vu z_k[u]

A hidden layer gives ELU(concat_k o_k + h w_res), its heads concatenated
(F_out = K F'); the last gives mean_k o_k + h w_res (F_out = F'), with no
activation. Without a skip the ``h w_res`` term is absent. There is no
bias. One head without a skip is the program's ``gat``.

The configuration's model keys ``heads`` (one count per layer, default 1)
and ``skip`` (one boolean per layer, default false) shape ``init`` and
the work counts; the reference reads K from ``att_src`` and the skip from
whether ``w_res`` is there.

Rounding (``reference.Layer``): the scores and the softmax run
element-wise in float32; ``h W`` and ``h w_res`` round as its ``matmul``;
the weighted sum rounds both its operands, the coefficients and ``z``, as
the SpMM's operands and sums them exactly; ``z`` and each head's sum are
stored.
"""
import math
from typing import NamedTuple, Tuple

import numpy as np
import scipy.sparse


class Shape(NamedTuple):
    """One layer as ``bench/work.py`` reads it."""
    fi: int                  # input width
    rows: int                # rows of ``w``
    fo: int                  # output width
    sum_width: int           # K F': the weighted sum runs over z
    self_loops: bool         # always: N(v) and v
    heads: int
    skip: bool

    def dense_work(self, v: int, e: int, b: int) -> Tuple[float, float]:
        """(FLOPs, bytes) of all but the weighted sum, for ``v`` vertices,
        ``e`` directed edges and a batch of ``b``: the transform h W, the
        skip's h w_res, the two score dot products per vertex and head, and
        per edge and head (self loops included) six element-wise
        operations: the score's add and LeakyReLU, and the softmax's
        subtract, exp, add and divide. Bytes: the weights read once, z
        written and read back once, and the two scores per vertex and head
        written and read back once."""
        kf, k = self.sum_width, self.heads
        res = self.fi * self.fo if self.skip else 0
        flops = b * (2.0 * v * (self.fi * kf + res) + 4.0 * v * kf
                     + 6.0 * (e + v) * k)
        nbytes = (4.0 * (self.fi * kf + 2 * kf + res)
                  + b * v * (8.0 * kf + 16.0 * k))
        return flops, nbytes


def layer_widths(widths, heads=None, skip=None):
    """Each layer as the work counts read it (``Shape``), with ``heads``
    and ``skip`` per layer as the configuration gives them."""
    n = len(widths) - 1
    heads = [1] * n if heads is None else [int(k) for k in heads]
    skip = [False] * n if skip is None else [bool(s) for s in skip]
    if len(heads) != n or len(skip) != n:
        raise ValueError(f"gat: {n} layers but heads {heads} and skip "
                         f"{skip}")
    out = []
    for i, (fi, fo, k, s) in enumerate(zip(widths[:-1], widths[1:], heads,
                                           skip)):
        if i == n - 1:
            kf = k * fo
        elif fo % k:
            raise ValueError(f"gat: layer {i} width {fo} is not {k} heads "
                             f"of one width")
        else:
            kf = fo
        out.append(Shape(fi, fi, fo, kf, True, k, s))
    return out


def init(key, widths, heads=None, skip=None):
    """Glorot-uniform weights, float32, each layer from four keys split off
    the one before (traced under jit)."""
    import jax
    import jax.numpy as jnp

    def glorot(k, shape):
        lim = math.sqrt(6.0 / (shape[0] + shape[1]))
        return jax.random.uniform(k, shape, jnp.float32, -lim, lim)

    out = []
    for s in layer_widths(widths, heads, skip):
        key, kw, ks, kd, kr = jax.random.split(key, 5)
        f = s.sum_width // s.heads
        p = {"w": glorot(kw, (s.fi, s.sum_width)),
             "att_src": glorot(ks, (s.heads, f)),
             "att_dst": glorot(kd, (s.heads, f))}
        if s.skip:
            p["w_res"] = glorot(kr, (s.fi, s.fo))
        out.append(p)
    return out


def _edges(adj):
    """Receiver and sender of each stored entry of a CSR adjacency."""
    counts = np.diff(adj.indptr)
    return np.repeat(np.arange(adj.shape[0]), counts), adj.indices


def _segments(adj, vals, ufunc, init):
    """``init`` [V, K] combined by ``ufunc`` with the rows of ``vals``
    [nnz, K] that each receiver's entries hold."""
    full = np.diff(adj.indptr) > 0
    out = init.copy()
    if full.any():
        out[full] = ufunc(out[full], ufunc.reduceat(
            vals, adj.indptr[:-1][full], axis=0))
    return out


def _leaky(x):
    return np.where(x > 0, x, np.float32(0.2) * x)


def reference_layer(p, x, last):
    """One layer of the reference over ``x`` (``reference.Layer``):
    (output rows, None: no row scale)."""
    k, f = p["att_src"].shape
    v = x.h.shape[0]

    def transform(rows):
        z = x.stored(x.matmul(rows, p["w"])).reshape(v, k, f)
        return z, np.einsum("vkf,kf->vk", z, p["att_src"])

    z_own, src_own = transform(x.h)
    dst = np.einsum("vkf,kf->vk", z_own, p["att_dst"])
    own = _leaky(dst + src_own)
    parts, top = [], own
    for adj, rows in x.parts:
        z, src = (z_own, src_own) if rows is x.h else transform(rows)
        r, s = _edges(adj)
        score = _leaky(dst[r] + src[s])
        top = _segments(adj, score, np.maximum, top)
        parts.append((adj, r, z, score))

    # The softmax per receiver, over every part's edges and the self loop.
    ex_own = np.exp(own - top)
    denom = ex_own.astype(np.float64)
    exps = []
    for adj, r, _, score in parts:
        exps.append(np.exp(score - top[r]))
        denom = _segments(adj, exps[-1].astype(np.float64), np.add, denom)
    denom = denom.astype(np.float32)

    out = np.empty((v, k, f), np.float32)
    for h in range(k):
        alpha = x.spmm_operand(ex_own[:, h] / denom[:, h])
        a = (alpha.astype(np.float64)[:, None]
             * x.spmm_operand(z_own[:, h]).astype(np.float64))
        for (adj, r, z, _), ex in zip(parts, exps):
            alpha = x.spmm_operand(ex[:, h] / denom[r, h])
            weighted = scipy.sparse.csr_matrix(
                (alpha.astype(np.float64), adj.indices, adj.indptr),
                shape=adj.shape)
            a = a + weighted @ x.spmm_operand(z[:, h]).astype(np.float64)
        out[:, h] = x.stored(a.astype(np.float32))
    if last:
        o = (out.astype(np.float64).sum(axis=1) / k).astype(np.float32)
    else:
        o = out.reshape(v, k * f)
    if "w_res" in p:
        o = o + x.matmul(x.h, p["w_res"])
    if not last:
        o = np.where(o > 0, o, np.expm1(np.minimum(o, 0.0)))
    return o.astype(np.float32), None

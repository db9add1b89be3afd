"""Public jit'd kernel wrappers with backend dispatch.

On TPU the Pallas kernels run; elsewhere (this CPU container, and for any
shape the kernels don't cover) a memory-safe chunked-XLA implementation with
identical math executes.  ``flash_attention_xla`` is a custom-VJP online-
softmax attention (flash fwd + flash bwd) so 32k+ sequences never
materialize the (Sq x Skv) score matrix and the backward saves only
(q, k, v, out, lse) — this is the path the multi-pod dry-run lowers.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ==========================================================================
# Flash attention (XLA chunked, custom VJP)
# ==========================================================================

_DEF_CHUNK = 512


def _mask(qpos, kpos, causal, window, seq_k):
    m = kpos < seq_k
    if causal:
        m &= qpos >= kpos
    if window:
        m &= (qpos - kpos) < window
    return m


def _fa_fwd_scan(q, k, v, causal, window, chunk):
    """q (B,Hkv,G,Sq,D); k,v (B,Hkv,Skv,D) -> out, lse (f32)."""
    B, Hkv, G, Sq, D = q.shape
    Skv = k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    nck = -(-Skv // chunk)
    pad = nck * chunk - Skv
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kp = kp.reshape(B, Hkv, nck, chunk, D).transpose(2, 0, 1, 3, 4)
    vp = vp.reshape(B, Hkv, nck, chunk, D).transpose(2, 0, 1, 3, 4)
    qpos = (jnp.arange(Sq) + (Skv - Sq))[:, None]

    def body(carry, inp):
        acc, m, l = carry
        j, kc, vc = inp
        s = jnp.einsum("bhgqd,bhcd->bhgqc", q, kc,
                       preferred_element_type=jnp.float32) * scale
        kpos = j * chunk + jnp.arange(chunk)[None, :]
        s = jnp.where(_mask(qpos, kpos, causal, window, Skv)[None, None, None],
                      s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # rows with all -inf so far keep m=-inf; exp(-inf - -inf) guarded:
        alpha = jnp.exp(jnp.where(m == -jnp.inf, -jnp.inf, m - m_new))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(jnp.isnan(p), 0.0, p)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqc,bhcd->bhgqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)
    m0 = jnp.full((B, Hkv, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), (jnp.arange(nck), kp, vp))
    lse = m + jnp.log(jnp.where(l == 0, 1.0, l))
    out = acc / jnp.where(l == 0, 1.0, l)[..., None]
    return out, lse


def _fa_bwd_scan(q, k, v, out, lse, dout, causal, window, chunk):
    B, Hkv, G, Sq, D = q.shape
    Skv = k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    nck = -(-Skv // chunk)
    pad = nck * chunk - Skv
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kp = kp.reshape(B, Hkv, nck, chunk, D).transpose(2, 0, 1, 3, 4)
    vp = vp.reshape(B, Hkv, nck, chunk, D).transpose(2, 0, 1, 3, 4)
    qpos = (jnp.arange(Sq) + (Skv - Sq))[:, None]
    # out is saved in compute dtype (bf16); accumulate delta in f32
    delta = jnp.einsum("bhgqd,bhgqd->bhgq", dout, out,
                       preferred_element_type=jnp.float32)

    def body(dq, inp):
        j, kc, vc = inp
        s = jnp.einsum("bhgqd,bhcd->bhgqc", q, kc,
                       preferred_element_type=jnp.float32) * scale
        kpos = j * chunk + jnp.arange(chunk)[None, :]
        msk = _mask(qpos, kpos, causal, window, Skv)[None, None, None]
        p = jnp.exp(s - lse[..., None])
        p = jnp.where(msk, p, 0.0)
        dv = jnp.einsum("bhgqc,bhgqd->bhcd", p, dout,
                        preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhgqd,bhcd->bhgqc", dout, vc,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhgqc,bhcd->bhgqd", ds.astype(kc.dtype), kc,
                             preferred_element_type=jnp.float32)
        dk = jnp.einsum("bhgqc,bhgqd->bhcd", ds.astype(q.dtype), q,
                        preferred_element_type=jnp.float32)
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, (jnp.arange(nck), kp, vp))
    dk = dks.transpose(1, 2, 0, 3, 4).reshape(B, Hkv, nck * chunk, D)[:, :, :Skv]
    dv = dvs.transpose(1, 2, 0, 3, 4).reshape(B, Hkv, nck * chunk, D)[:, :, :Skv]
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_xla(q, k, v, causal=True, window=0, chunk=_DEF_CHUNK):
    out, _ = _fa_fwd_scan(q, k, v, causal, window, chunk)
    return out.astype(q.dtype)


def _fa_vjp_fwd(q, k, v, causal, window, chunk):
    out, lse = _fa_fwd_scan(q, k, v, causal, window, chunk)
    out = out.astype(q.dtype)
    # residuals stay in compute dtype: an f32 `out` here gets stacked per
    # layer by the training scan (+10GB/chip on qwen2-72b; see §Perf)
    return out, (q, k, v, out, lse)


def _fa_vjp_bwd(causal, window, chunk, res, dout):
    q, k, v, out, lse = res
    dq, dk, dv = _fa_bwd_scan(q, k, v, out, lse, dout.astype(q.dtype),
                              causal, window, chunk)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention_xla.defvjp(_fa_vjp_fwd, _fa_vjp_bwd)


# ==========================================================================
# Dispatchers
# ==========================================================================

def attention(q, k, v, *, causal=True, sliding_window=0):
    """q (B,Sq,Hq,D); k,v (B,Skv,Hkv,D) -> (B,Sq,Hq,D)."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    if on_tpu() and Sq >= 128 and Skv >= 128:
        from repro.kernels.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal,
                               sliding_window=sliding_window)
    if max(Sq, Skv) <= 1024:
        return _ref.attention_ref(q, k, v, causal=causal,
                                  sliding_window=sliding_window)
    G = Hq // Hkv
    qg = q.transpose(0, 2, 1, 3).reshape(B, Hkv, G, Sq, D)
    kg = k.transpose(0, 2, 1, 3)
    vg = v.transpose(0, 2, 1, 3)
    out = flash_attention_xla(qg, kg, vg, causal, sliding_window,
                              min(_DEF_CHUNK, Skv))
    return out.reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)


def decode_attention(q, cache_k, cache_v, pos, *, lengths=None,
                     sliding_window=0):
    """Single-token decode over a (possibly ring-buffered) KV cache."""
    return _ref.decode_attention_ref(q, cache_k, cache_v, pos,
                                     lengths=lengths,
                                     sliding_window=sliding_window)


def decode_attention_partial(q, cache_k, cache_v, valid):
    """Per-shard partial attention stats for sequence-parallel decode.

    q (B,1,Hq,D); cache (B,Sloc,Hkv,D); valid (B,Sloc) bool.
    Returns (acc (B,Hq,D) f32 unnormalized, m (B,Hq) f32, l (B,Hq) f32) —
    combined across shards by ``parallel.sp.sp_decode_attention``.
    """
    B, Sloc, Hkv, D = cache_k.shape
    Hq = q.shape[2]
    G = Hq // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, D)
    kf = cache_k.astype(jnp.float32).transpose(0, 2, 1, 3)
    vf = cache_v.astype(jnp.float32).transpose(0, 2, 1, 3)
    s = jnp.einsum("bhgd,bhkd->bhgk", qf, kf) * scale
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(jnp.isnan(p), 0.0, p)           # all-masked shard
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhgk,bhkd->bhgd", p, vf)
    return (acc.reshape(B, Hq, D), m.reshape(B, Hq), l.reshape(B, Hq))


def selective_scan(x, dt, A, Bc, Cc, D_skip, *, chunk=128):
    """Mamba-1 scan.  Chunked associative scan on XLA; Pallas kernel on TPU."""
    if on_tpu() and x.shape[1] % chunk == 0 and x.shape[2] % 256 == 0:
        from repro.kernels.selective_scan import selective_scan as pallas_scan
        return pallas_scan(x, dt, A, Bc, Cc, D_skip, chunk=chunk)
    return _chunked_selective_scan(x, dt, A, Bc, Cc, D_skip, chunk=chunk)


def _chunked_selective_scan(x, dt, A, Bc, Cc, D_skip, *, chunk=128):
    """Vectorized scan: outer lax.scan over chunks, inner associative scan.

    Never materializes (B,S,Di,N); peak intermediate is (B,chunk,Di,N).
    """
    B, S, Di = x.shape
    N = A.shape[1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bc = jnp.pad(Bc, ((0, 0), (0, pad), (0, 0)))
        Cc = jnp.pad(Cc, ((0, 0), (0, pad), (0, 0)))
    nc = (S + pad) // chunk
    xs = x.reshape(B, nc, chunk, Di).transpose(1, 0, 2, 3)
    dts = dt.reshape(B, nc, chunk, Di).transpose(1, 0, 2, 3)
    bs = Bc.reshape(B, nc, chunk, N).transpose(1, 0, 2, 3)
    cs = Cc.reshape(B, nc, chunk, N).transpose(1, 0, 2, 3)
    Af = A.astype(jnp.float32)
    Df = D_skip.astype(jnp.float32)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    def chunk_step(h, inp):
        xc, dtc, bc, cc = inp                         # (B,chunk,*)
        dtf = dtc.astype(jnp.float32)
        da = jnp.exp(dtf[..., None] * Af[None, None])             # (B,L,Di,N)
        dbx = (dtf * xc.astype(jnp.float32))[..., None] * bc.astype(
            jnp.float32)[:, :, None, :]
        a_all, h_all = jax.lax.associative_scan(combine, (da, dbx), axis=1)
        h_all = h_all + a_all * h[:, None]            # inject carry-in state
        y = jnp.einsum("bldn,bln->bld", h_all, cc.astype(jnp.float32))
        y = y + xc.astype(jnp.float32) * Df[None, None]
        return h_all[:, -1], y.astype(x.dtype)

    h0 = jnp.zeros((B, Di, N), jnp.float32)
    # remat the chunk body: AD saves only the (B,Di,N) carry per chunk and
    # recomputes the (B,chunk,Di,N) intermediates in the backward pass.
    _, ys = jax.lax.scan(jax.checkpoint(chunk_step), h0, (xs, dts, bs, cs))
    y = ys.transpose(1, 0, 2, 3).reshape(B, nc * chunk, Di)
    return y[:, :S] if pad else y


def ssm_decode(h, x, dt, A, Bc, Cc, D_skip):
    return _ref.ssm_decode_ref(h, x, dt, A, Bc, Cc, D_skip)


def assign_tasks(loads, costs):
    """Two-stage min-search task mapping (paper Sec 4.1).

    Always routes through the Pallas kernel — compiled on TPU,
    ``interpret=True`` elsewhere — so the batch mapping path exercises
    the exact kernel the hardware runs (decision-for-decision equal to
    the pure-JAX oracle, tests/test_kernels_minsearch.py)."""
    from repro.kernels.hier_minsearch import assign_tasks as pallas_assign
    return pallas_assign(loads, costs, interpret=not on_tpu())

"""Shared model layers (pure JAX, no framework deps).

Memory discipline for long sequences (DESIGN.md §6): attention is a
flash-style *pair scan* — an ordered scan over (q-chunk, kv-chunk) block
pairs with running max/sum softmax state, emitting only causal pairs so HLO
FLOPs match causal-optimal cost (no masked half-square waste); the loss is
a chunked-vocab cross entropy so (B, T, V) logits never materialize.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# norms / activations / rope
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    out = ((xf * scale) * w.astype(jnp.float32)).astype(x.dtype)
    # pin the bf16 cast here: without the barrier XLA hoists the fp32->bf16
    # convert past the TP collectives and moves activations over ICI in
    # fp32 — 2x the wire bytes (EXPERIMENTS.md §Perf i3)
    return jax.lax.optimization_barrier(out)


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, H, T, dh); positions: (T,) or (B, T)."""
    dh = x.shape[-1]
    inv = rope_freqs(dh, theta)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., T, dh/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if positions.ndim == 1:
        cos, sin = cos[None, None], sin[None, None]
    else:  # (B, T, dh/2) -> (B, 1, T, dh/2)
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def _einsum_f32(sub: str, a, b):
    """bf16 x bf16 -> f32 einsum.

    TPU: native MXU mixed-precision via preferred_element_type.  CPU: the
    XLA-CPU DotThunk cannot *execute* BF16xBF16=F32 for these shapes, so
    cast inputs (the converts fold; CPU is the validation substrate only).
    """
    if jax.default_backend() == "cpu":
        return jnp.einsum(sub, a.astype(jnp.float32), b.astype(jnp.float32))
    return jnp.einsum(sub, a, b, preferred_element_type=jnp.float32)


def _block_attn_update(q_i, k_j, v_j, m, l, acc, mask=None, scale=1.0):
    """One online-softmax block update.

    q_i: (B, G, r, qc, dh); k_j/v_j: (B, G, kc, dh);
    m,l: (B, G, r, qc); acc: (B, G, r, qc, dh) fp32.
    """
    s = jnp.einsum(
        "bgrqd,bgkd->bgrqk", q_i, k_j, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows (m_new == -inf)
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(jnp.where(jnp.isfinite(m), m - safe_m, -jnp.inf))
    corr = jnp.where(jnp.isfinite(corr), corr, 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bgrqk,bgkd->bgrqd", p.astype(v_j.dtype), v_j,
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
) -> jnp.ndarray:
    """Blockwise GQA attention.  q: (B, Hq, Tq, dh); k,v: (B, G, Tk, dh).

    Per-q-chunk *segments*: an unrolled loop over q chunks, each carrying
    only a chunk-local (B, G, r, qc, dh) online-softmax state through an
    inner scan over exactly the causally-visible kv chunks (static count
    per segment).  Compared to a single scan with full-length state this
    keeps emitted FLOPs causal-optimal AND keeps the fp32 accumulator
    chunk-sized — the scan transpose (backward) then accumulates
    chunk-local too, which removes the full-(B,H,T,dh) fp32 collectives
    GSPMD otherwise emits around the loop state (EXPERIMENTS.md §Perf i2).
    """
    from repro.distributed.ctx import constrain

    B, Hq, Tq, dh = q.shape
    G, Tk = k.shape[1], k.shape[2]
    r = Hq // G
    q_chunk = min(q_chunk, Tq)
    kv_chunk = min(kv_chunk, Tk)
    if Tq % q_chunk:  # ragged (small tests): single q block
        q_chunk = Tq
    if Tk % kv_chunk:
        kv_chunk = Tk
    nq, nk = Tq // q_chunk, Tk // kv_chunk
    qg = q.reshape(B, G, r, Tq, dh)
    # keep a head dim model-sharded through the scan: without the pin,
    # GSPMD replicates the attention math across the model axis.  Shard G
    # when it divides the axis (KV stays sharded too); otherwise shard the
    # per-group repeat dim r and let the small KV replicate — pinning a
    # non-dividing G measurably backfires (EXPERIMENTS.md §Perf qwen3 i2).
    from repro.distributed.ctx import axis_size

    ms = axis_size("model")
    if ms > 1 and G % ms == 0:
        qg = constrain(qg, "data", "model", None, None, None)
        k = constrain(k, "data", "model", None, None)
        v = constrain(v, "data", "model", None, None)
    elif ms > 1 and r % ms == 0:
        # G doesn't divide (e.g. qwen3 kv=4 on model=16): shard the repeat
        # dim; small KV replicates — pinning uneven G measurably backfires
        qg = constrain(qg, "data", None, "model", None, None)
    elif ms > 1 and ms % G == 0:
        # uneven-but-contained G (kv=8 on model=16): measured -54% executed
        # FLOPs / -46% collectives on llama3-8b train_4k (§Perf i1)
        qg = constrain(qg, "data", "model", None, None, None)
        k = constrain(k, "data", "model", None, None)
        v = constrain(v, "data", "model", None, None)
    scale = 1.0 / np.sqrt(dh)

    # causal offset: queries are the *last* Tq positions of the Tk context
    off = Tk - Tq
    k_pos = jnp.arange(kv_chunk)

    outs = []
    for i in range(nq):
        q_i = qg[:, :, :, i * q_chunk : (i + 1) * q_chunk]
        if causal:
            last_q = off + (i + 1) * q_chunk - 1
            n_vis = min(last_q // kv_chunk + 1, nk)  # static per segment
        else:
            n_vis = nk
        m0 = jnp.full((B, G, r, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, G, r, q_chunk), jnp.float32)
        acc0 = jnp.zeros((B, G, r, q_chunk, dh), jnp.float32)
        gq = off + i * q_chunk + jnp.arange(q_chunk)

        def step(carry, j, q_i=q_i, gq=gq):
            m, l, acc = carry
            k_j = jax.lax.dynamic_slice_in_dim(k, j * kv_chunk, kv_chunk, axis=2)
            v_j = jax.lax.dynamic_slice_in_dim(v, j * kv_chunk, kv_chunk, axis=2)
            if causal:
                gk = j * kv_chunk + k_pos
                mask = (gq[:, None] >= gk[None, :])[None, None, None]
            else:
                mask = None
            m, l, acc = _block_attn_update(q_i, k_j, v_j, m, l, acc, mask, scale)
            return (m, l, acc), None

        (m, l, acc), _ = jax.lax.scan(
            step, (m0, l0, acc0), jnp.arange(n_vis)
        )
        outs.append(acc / jnp.maximum(l[..., None], 1e-30))

    out = jnp.concatenate(outs, axis=3)
    return out.reshape(B, Hq, Tq, dh).astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    length: jnp.ndarray,
    kv_chunk: int = 2048,
) -> jnp.ndarray:
    """Single-token attention against a KV cache — flash-decoding style.

    q: (B, Hq, 1, dh); caches: (B, G, S, dh); length: () or (B,) valid kv
    count.  The sequence axis is split into segments computed *in
    parallel* (each segment's online-softmax partials are tiny), then
    combined with a max/logsumexp merge.  With the cache sharded over the
    model axis on S, every segment's math is device-local and only the
    (B, G, r, dh)-sized partials cross ICI — the KV cache itself never
    moves (§Perf decode iteration).
    """
    from repro.distributed.ctx import constrain

    B, Hq, _, dh = q.shape
    G, S = k_cache.shape[1], k_cache.shape[2]
    r = Hq // G
    kv_chunk = min(kv_chunk, S)
    if S % kv_chunk:  # ragged tail (small tests): pad; masked out below
        pad = kv_chunk - S % kv_chunk
        k_cache = jnp.concatenate(
            [k_cache, jnp.zeros((B, G, pad, dh), k_cache.dtype)], axis=2
        )
        v_cache = jnp.concatenate(
            [v_cache, jnp.zeros((B, G, pad, dh), v_cache.dtype)], axis=2
        )
        S += pad
    ns, sc = S // kv_chunk, kv_chunk
    qg = q.reshape(B, G, r, dh)
    k5 = constrain(k_cache.reshape(B, G, ns, sc, dh),
                   "data", None, "model", None, None)
    v5 = constrain(v_cache.reshape(B, G, ns, sc, dh),
                   "data", None, "model", None, None)
    scale = 1.0 / np.sqrt(dh)
    length = jnp.asarray(length)
    lb = length if length.ndim else length[None].repeat(B, 0)  # (B,)

    s = _einsum_f32("bgrd,bgscd->bgrsc", qg, k5) * scale
    pos = (jnp.arange(ns) * sc)[:, None] + jnp.arange(sc)[None, :]  # (ns, sc)
    mask = (pos[None] < lb[:, None, None])[:, None, None]  # (B,1,1,ns,sc)
    s = jnp.where(mask, s, -jnp.inf)
    m_s = jnp.max(s, axis=-1)  # (B,G,r,ns)
    safe = jnp.where(jnp.isfinite(m_s), m_s, 0.0)
    p = jnp.where(mask, jnp.exp(s - safe[..., None]), 0.0)
    l_s = jnp.sum(p, axis=-1)  # (B,G,r,ns)
    acc_s = _einsum_f32("bgrsc,bgscd->bgrsd", p.astype(v5.dtype), v5)
    # merge segments (the only cross-segment — hence cross-device — math)
    m = jnp.max(m_s, axis=-1, keepdims=True)  # (B,G,r,1)
    w = jnp.where(jnp.isfinite(m_s), jnp.exp(m_s - jnp.where(
        jnp.isfinite(m), m, 0.0)), 0.0)  # (B,G,r,ns)
    l = jnp.sum(w * l_s, axis=-1)  # (B,G,r)
    out = jnp.sum(w[..., None] * acc_s, axis=3) / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Hq, 1, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def chunked_softmax_xent(
    h: jnp.ndarray,
    lm_head: jnp.ndarray,
    labels: jnp.ndarray,
    mask: jnp.ndarray | None = None,
    chunk: int = 512,
    z_loss: float = 0.0,
) -> jnp.ndarray:
    """Cross entropy without materializing (B, T, V) logits.

    h: (B, T, d); lm_head: (d, V); labels: (B, T) int32.  Scans T in chunks
    computing per-chunk logits in fp32.
    """
    B, T, d = h.shape
    chunk = min(chunk, T)
    assert T % chunk == 0
    if mask is None:
        mask = jnp.ones((B, T), jnp.float32)

    def step(carry, idx):
        tot, cnt = carry
        h_c = jax.lax.dynamic_slice_in_dim(h, idx * chunk, chunk, axis=1)
        y_c = jax.lax.dynamic_slice_in_dim(labels, idx * chunk, chunk, axis=1)
        m_c = jax.lax.dynamic_slice_in_dim(mask, idx * chunk, chunk, axis=1)
        logits = jnp.einsum(
            "btd,dv->btv", h_c, lm_head, preferred_element_type=jnp.float32
        )
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y_c[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * m_c
        if z_loss:
            nll = nll + z_loss * (lse * lse) * m_c
        return (tot + jnp.sum(nll), cnt + jnp.sum(m_c)), None

    (tot, cnt), _ = jax.lax.scan(
        step, (jnp.float32(0), jnp.float32(0)), jnp.arange(T // chunk)
    )
    return tot / jnp.maximum(cnt, 1.0)

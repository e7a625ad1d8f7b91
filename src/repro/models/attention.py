"""GQA attention: chunked-exact XLA path, Pallas dispatch, decode w/ KV cache.

Three execution paths, one set of semantics (causal, sliding window, logit
softcap, GQA):

* ``full_attention`` — training/prefill.  On TPU dispatches to the Pallas
  flash kernel; elsewhere an exact memory-efficient XLA implementation
  (scan over KV chunks with the online-softmax recurrence) so 32k-token
  shapes lower on the CPU dry-run without materialising [Sq, Sk].
* ``decode_attention`` — one query against a KV cache.
* ``decode_attention_seq_sharded`` — same, with the cache *sequence* sharded
  over the ``model`` mesh axis (for archs whose few KV heads cannot be
  head-sharded, e.g. glm4's 2 KV heads): each shard computes a partial
  softmax and the shards merge with a log-sum-exp reduction (flash-decode
  adapted to shard_map collectives).

Never repeats KV heads in memory: queries reshape to [B, Hkv, G, S, D].
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import sharding

NEG = -1e30


class KVCache(NamedTuple):
    k: jax.Array        # [B, Hkv, S, D]
    v: jax.Array        # [B, Hkv, S, D]
    length: jax.Array   # i32 scalar: valid prefix length


def init_cache(batch: int, kv_heads: int, max_len: int, head_dim: int,
               dtype=jnp.bfloat16) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, kv_heads, max_len, head_dim), dtype),
        v=jnp.zeros((batch, kv_heads, max_len, head_dim), dtype),
        length=jnp.int32(0),
    )


def cache_update(cache: KVCache, k_new: jax.Array, v_new: jax.Array
                 ) -> KVCache:
    """Append [B, Hkv, T, D] at the current length."""
    t = k_new.shape[2]
    k = jax.lax.dynamic_update_slice(
        cache.k, k_new.astype(cache.k.dtype), (0, 0, cache.length, 0))
    v = jax.lax.dynamic_update_slice(
        cache.v, v_new.astype(cache.v.dtype), (0, 0, cache.length, 0))
    return KVCache(k=k, v=v, length=cache.length + t)


# ---------------------------------------------------------------------------
# training / prefill attention
# ---------------------------------------------------------------------------


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window=0, softcap: float = 0.0,
                   kv_offset: int = 0, chunk: int = 1024,
                   use_flash: Optional[bool] = None) -> jax.Array:
    """q [B, Hq, Sq, D]; k, v [B, Hkv, Sk, D].  ``window`` may be a traced
    scalar (0 = full attention) so alternating local/global layers can share
    one scanned layer body."""
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    if use_flash and isinstance(window, int):
        from repro.kernels.flash_attention.ops import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, kv_offset=kv_offset)
    return _chunked_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, kv_offset=kv_offset,
                              chunk=chunk)


def _chunked_attention(q, k, v, *, causal, window, softcap, kv_offset,
                       chunk) -> jax.Array:
    """Exact online-softmax attention, scanning KV chunks (XLA path).

    optflags (§Perf O2): ``strided_gqa`` lays query heads out as
    [groups, kv_heads] so the group dim carries the head sharding when
    Hkv < mesh; ``bf16_scores`` feeds the two dots bf16 with f32
    accumulation; ``additive_mask`` folds the causal/window mask into one
    broadcast [Sq, chunk] bias instead of materialised per-head selects.
    """
    from repro.models.optflags import flags
    fl = flags()
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    scale = d ** -0.5
    chunk = min(chunk, sk)
    while sk % chunk:        # largest divisor <= requested chunk
        chunk -= 1
    n_chunks = sk // chunk

    cdt = jnp.bfloat16 if fl.bf16_scores else jnp.float32
    if fl.strided_gqa:
        # head h = g_idx * Hkv + kv_idx: outer dim g inherits head sharding
        qf = q.reshape(b, g, hkv, sq, d).astype(cdt) * scale
        eq_s = "bghqd,bhkd->bghqk"
        eq_o = "bghqk,bhkd->bghqd"
    else:
        qf = q.reshape(b, hkv, g, sq, d).astype(cdt) * scale
        eq_s = "bhgqd,bhkd->bhgqk"
        eq_o = "bhgqk,bhkd->bhgqd"
    kc = k.reshape(b, hkv, n_chunks, chunk, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, n_chunks, chunk, d).transpose(2, 0, 1, 3, 4)

    qpos = (jnp.arange(sq) + kv_offset)[:, None]          # [Sq, 1]
    win = jnp.asarray(window)

    # no mask at all for non-causal, windowless attention (encoders):
    # even an all-true mask costs a materialised broadcast per chunk
    need_mask = causal or not (isinstance(window, int) and window == 0)

    def body(carry, xs):
        m, l, acc = carry
        kj, vj, j = xs
        s = jnp.einsum(eq_s, qf, kj.astype(cdt),
                       preferred_element_type=jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = (j * chunk + jnp.arange(chunk))[None, :]   # [1, chunk]
        mask = jnp.ones((sq, chunk), bool)
        if causal:
            mask &= kpos <= qpos
        if need_mask:
            mask &= jnp.where(win > 0, kpos > qpos - win, True)
            if fl.additive_mask:
                s = s + jnp.where(mask, 0.0, NEG)         # one broadcast
            else:
                s = jnp.where(mask, s, NEG)
        m_cur = s.max(-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - jnp.where(m_new <= NEG / 2, 0.0, m_new))
        if need_mask and not fl.additive_mask:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(jnp.where(m <= NEG / 2, NEG, m - m_new))
        alpha = jnp.where(m_new <= NEG / 2, 0.0, alpha)
        l_new = l * alpha + p.sum(-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            eq_o, p.astype(cdt), vj.astype(cdt),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    hshape = (b, g, hkv) if fl.strided_gqa else (b, hkv, g)
    m0 = jnp.full(hshape + (sq, 1), NEG, jnp.float32)
    l0 = jnp.zeros(hshape + (sq, 1), jnp.float32)
    a0 = jnp.zeros(hshape + (sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(b, hq, sq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


def _gqa_layout():
    """(reshape order, score einsum, out einsum) per the strided_gqa flag."""
    from repro.models.optflags import flags
    if flags().strided_gqa:
        return True, "bghtd,bhsd->bghts", "bghts,bhsd->bghtd"
    return False, "bhgtd,bhsd->bhgts", "bhgts,bhsd->bhgtd"


def _decode_scores(q, k, eq, *, softcap, scale):
    s = jnp.einsum(eq, q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    return s


def decode_attention(q: jax.Array, cache: KVCache, *, window=0,
                     softcap: float = 0.0) -> jax.Array:
    """q [B, Hq, T, D] (T = new tokens, usually 1) vs the cached prefix.

    Assumes the new tokens' K/V are already appended: valid positions are
    ``< cache.length``; query i sits at absolute position
    ``cache.length - T + i``.
    """
    b, hq, t, d = q.shape
    hkv, s = cache.k.shape[1], cache.k.shape[2]
    g = hq // hkv
    strided, eq_s, eq_o = _gqa_layout()
    qr = q.reshape((b, g, hkv, t, d) if strided else (b, hkv, g, t, d))
    sc = _decode_scores(qr, cache.k, eq_s, softcap=softcap, scale=d ** -0.5)
    qpos = cache.length - t + jnp.arange(t)               # [T]
    kpos = jnp.arange(s)                                  # [S]
    mask = kpos[None, :] <= qpos[:, None]
    win = jnp.asarray(window)
    mask &= jnp.where(win > 0, kpos[None, :] > qpos[:, None] - win, True)
    sc = jnp.where(mask, sc, NEG)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum(eq_o, p, cache.v.astype(jnp.float32))
    return out.reshape(b, hq, t, d).astype(q.dtype)


def decode_attention_seq_sharded(q: jax.Array, cache: KVCache, mesh: Mesh, *,
                                 axis: str = "model", window=0,
                                 softcap: float = 0.0) -> jax.Array:
    """Flash-decode over a sequence-sharded cache.

    The cache's S dim is sharded over ``axis``; each shard computes a
    partial (max, denom, numerator) and shards merge via an LSE reduction —
    three small collectives instead of all-gathering a multi-GB cache.
    """
    hq = q.shape[1]
    hkv, s_global = cache.k.shape[1], cache.k.shape[2]
    g = hq // hkv
    n_shards = mesh.shape[axis]
    s_local = s_global // n_shards
    # batch stays sharded over the data(/pod) axes inside the shard_map
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names
                  and q.shape[0] % mesh.shape[a] == 0)
    bspec = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)

    strided, eq_s, eq_o = _gqa_layout()

    def partial_attn(q_l, k_l, v_l, length):
        bl, _, t, d = q_l.shape
        shard = jax.lax.axis_index(axis)
        qr = q_l.reshape((bl, g, hkv, t, d) if strided
                         else (bl, hkv, g, t, d))
        sc = _decode_scores(qr, k_l, eq_s, softcap=softcap, scale=d ** -0.5)
        qpos = length - t + jnp.arange(t)
        kpos = shard * s_local + jnp.arange(s_local)
        mask = kpos[None, :] <= qpos[:, None]
        win = jnp.asarray(window)
        mask &= jnp.where(win > 0, kpos[None, :] > qpos[:, None] - win, True)
        sc = jnp.where(mask, sc, NEG)
        m = sc.max(-1, keepdims=True)                     # [b,hkv,g,t,1]
        m_glob = jax.lax.pmax(m, axis)
        p = jnp.exp(sc - jnp.where(m_glob <= NEG / 2, 0.0, m_glob))
        p = jnp.where(mask, p, 0.0)
        l = jax.lax.psum(p.sum(-1, keepdims=True), axis)
        o = jnp.einsum(eq_o, p, v_l.astype(jnp.float32))
        o = jax.lax.psum(o, axis)
        out = o / jnp.maximum(l, 1e-30)
        return out.reshape(bl, hq, t, d).astype(q_l.dtype)

    q_spec = P(bspec, None, None, None)
    kv_spec = P(bspec, None, axis, None)
    fn = shard_map(partial_attn, mesh=mesh,
                   in_specs=(q_spec, kv_spec, kv_spec, P()),
                   out_specs=q_spec, check_vma=False)
    return fn(q, cache.k, cache.v, cache.length)

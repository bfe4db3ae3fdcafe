"""Plain references the benchmark compares the program with.

Nothing here imports the program. The SGNS step follows the FULL-W2V
schedule (paper §3; the sequential kernel's order): sentences one after
another, in each the positions left to right, a ring of ``2W_f + 1``
input rows that a position enters once and leaves once, and every window
updated from its pre-window values with the two small GEMMs of the
shared-negative update. The dense top-k ranks cosine scores by
``(score desc, id asc)`` over the whole table.

Each takes its matrix product as an argument: :func:`mm_f32` is the
configuration's own precision (float32 at ``HIGHEST``), the others are the
controls a step down from it, written out as roundings of the operands so
that they compute the same on every backend: :func:`mm_bf16x3` (three
bf16 passes, what ``Precision.HIGH`` does on a TPU) for training, and
:func:`mm_int8` / :func:`mm_fp8` for serving, whose configured precision
is one bf16 pass (:func:`mm_bf16`).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def mm_f32(a, b):
    return jnp.matmul(a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _round(x, dtype):
    """``x`` rounded to ``dtype`` and back to f32."""
    return x.astype(dtype).astype(jnp.float32)


def mm_bf16(a, b):
    """One bf16 pass: operands rounded to bf16, products summed in f32
    (products of bf16 values are exact in f32)."""
    return mm_f32(_round(a, jnp.bfloat16), _round(b, jnp.bfloat16))


def mm_bf16x3(a, b):
    """Three bf16 passes, hi*hi + hi*lo + lo*hi, summed in f32."""
    ah, bh = _round(a, jnp.bfloat16), _round(b, jnp.bfloat16)
    al, bl = _round(a - ah, jnp.bfloat16), _round(b - bh, jnp.bfloat16)
    return mm_f32(ah, bh) + (mm_f32(ah, bl) + mm_f32(al, bh))


def round_e4m3(x):
    """``x`` rounded to the nearest float8 e4m3 value (3 mantissa bits,
    subnormal step 2^-9, saturating at 448), computed in f32 arithmetic:
    a cast through ``float8_e4m3fn`` is not rounded alike on every
    backend."""
    mag = jnp.abs(x)
    exp = jnp.floor(jnp.log2(jnp.maximum(mag, 2.0 ** -9)))
    step = jnp.exp2(jnp.maximum(exp, -6.0) - 3.0)
    return jnp.clip(jnp.round(x / step) * step, -448.0, 448.0)


def mm_fp8(a, b):
    """Operands rounded to float8 (e4m3), products summed in f32."""
    return mm_f32(round_e4m3(a), round_e4m3(b))


def _int8_rows(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale), scale


def mm_int8(a, b):
    """Rows of ``a`` and columns of ``b`` quantized to int8 with one scale
    each; integer products summed exactly, then rescaled."""
    qa, sa = _int8_rows(a, axis=1)
    qb, sb = _int8_rows(b, axis=0)
    return mm_f32(qa, qb) * sa * sb


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def input_rows(key, vocab: int, dim: int):
    """word2vec's initial input rows, ~ U(-0.5/d, 0.5/d)."""
    return (jax.random.uniform(key, (vocab, dim), jnp.float32) - 0.5) / dim


@functools.partial(jax.jit, static_argnums=(1, 2))
def init_tables(key, vocab: int, dim: int):
    """word2vec's initialisation: input rows :func:`input_rows`, output
    rows zero."""
    return input_rows(key, vocab, dim), jnp.zeros((vocab, dim), jnp.float32)


def sigmoid(x):
    return jnp.where(x >= 0, 1.0 / (1.0 + jnp.exp(-x)),
                     jnp.exp(x) / (1.0 + jnp.exp(x)))


def window_delta(ctx, out_rows, mask, lr, mm):
    """One window: pairings from pre-window values, deltas at its end.
    ``out_rows[0]`` is the target, the rest are negatives."""
    label = jnp.zeros((out_rows.shape[0],), jnp.float32).at[0].set(1.0)
    corr = mm(ctx, out_rows.T)
    g = lr * (label[None, :] - sigmoid(corr))
    g = jnp.where(mask[:, None], g, 0.0)
    return mm(g, out_rows), mm(g.T, ctx)


def _sentence(w_in, w_out, tokens, negs, length, lr, w_f, mm):
    L = negs.shape[0]
    r = 2 * w_f + 1
    offsets = jnp.array([o for o in range(-w_f, w_f + 1) if o != 0],
                        jnp.int32)
    buf = jnp.zeros((r, w_in.shape[1]), w_in.dtype)
    for q in range(min(w_f, L)):
        buf = buf.at[q % r].set(jnp.where(q < length, w_in[tokens[q]],
                                          buf[q % r]))

    def position(t, carry):
        w_in, w_out, buf = carry
        q = t + w_f
        load = q < length
        old = q - r
        old_c = jnp.clip(old, 0, L - 1)
        store_at = tokens[old_c]
        w_in = w_in.at[store_at].set(jnp.where(
            load & (old >= 0), buf[old_c % r], w_in[store_at]))
        q_c = jnp.clip(q, 0, L - 1)
        buf = buf.at[q_c % r].set(jnp.where(load, w_in[tokens[q_c]],
                                            buf[q_c % r]))
        p = t + offsets
        mask = (p >= 0) & (p < length)
        slots = jnp.mod(p, r)
        out_idx = jnp.concatenate([tokens[t][None], negs[t]])
        d_ctx, d_out = window_delta(buf[slots], w_out[out_idx], mask, lr,
                                    mm)
        buf = buf.at[slots].add(d_ctx)
        w_out = w_out.at[out_idx].add(d_out)
        return w_in, w_out, buf

    # positions past the sentence's end change nothing: stop at its length
    w_in, w_out, buf = jax.lax.fori_loop(0, length, position,
                                         (w_in, w_out, buf))

    def flush(k, w_in):
        p = length - r + k
        p_c = jnp.clip(p, 0, L - 1)
        idx = tokens[p_c]
        return w_in.at[idx].set(jnp.where(p >= 0, buf[jnp.mod(p_c, r)],
                                          w_in[idx]))

    return jax.lax.fori_loop(0, r, flush, w_in), w_out


@functools.partial(jax.jit, static_argnums=(6, 7), donate_argnums=(0, 1))
def sgns_step(w_in, w_out, tokens, negs, lengths, lr, w_f: int,
              mm: Callable = mm_f32):
    """One batch: ``tokens (S, L)``, ``negs (S, L, N)``, ``lengths (S,)``."""
    def body(carry, xs):
        t, n, ln = xs
        return _sentence(*carry, t, n, ln, lr, w_f, mm), None

    (w_in, w_out), _ = jax.lax.scan(body, (w_in, w_out),
                                    (tokens, negs, lengths))
    return w_in, w_out


def replicated_step(w_in, w_out, tokens, negs, lengths, lr, w_f: int,
                    mm: Callable = mm_f32, devices=None):
    """One batch trained data parallel (Hogwild averaging), a replica on
    each of ``devices``: the batch's rows split into as many equal
    contiguous blocks, each trained by :func:`sgns_step` from the same
    tables on its own device, and the replicas' tables summed in replica
    order on the first device and divided by their number. A device of
    its own each keeps one replica's tables, not all of them, beside the
    sum. With one device (or none named) it is :func:`sgns_step`."""
    if devices is None or len(devices) == 1:
        return sgns_step(w_in, w_out, tokens, negs, lengths, lr, w_f, mm)
    n = len(devices)
    per = tokens.shape[0] // n
    outs = [sgns_step(*jax.device_put(
        (w_in, w_out, tokens[r * per:(r + 1) * per],
         negs[r * per:(r + 1) * per], lengths[r * per:(r + 1) * per], lr),
        dev, may_alias=False), w_f, mm) for r, dev in enumerate(devices)]
    total = outs.pop(0)
    while outs:
        total = tuple(a + b for a, b in zip(
            total, jax.device_put(outs.pop(0), devices[0])))
    return tuple(t / n for t in total)


def unpad(tokens: np.ndarray, negs: np.ndarray, lengths: np.ndarray,
          multiple: int = 8):
    """The batch cut to its longest sentence (rounded up): the reference
    never reads past a sentence's length, so only the shape shrinks."""
    cut = -(-max(int(lengths.max()), 1) // multiple) * multiple
    cut = min(cut, tokens.shape[1])
    return tokens[:, :cut], negs[:, :cut], lengths


@functools.partial(jax.jit, static_argnums=(5,))
def _loss_chunk(w_in, w_out, ctx, ctx_ok, outs, mm):
    c = w_in[ctx]                                   # (P, K, d)
    o = w_out[outs]                                 # (P, M, d)
    corr = jax.vmap(lambda a, b: mm(a, b.T))(c, o)  # (P, K, M)
    sign = jnp.where(jnp.arange(o.shape[1]) == 0, 1.0, -1.0)
    ll = jax.nn.log_sigmoid(corr * sign)
    return -jnp.sum(jnp.where(ctx_ok[:, :, None], ll, 0.0))


def window_index(tokens: np.ndarray, negs: np.ndarray, lengths: np.ndarray,
                 w_f: int):
    """Host index arrays over every real window of a batch: context ids
    ``(P, 2W_f)`` with their validity, and output ids ``(P, N+1)``."""
    S, L = tokens.shape
    s, t = np.nonzero(np.arange(L)[None, :] < lengths[:, None])
    off = np.array([o for o in range(-w_f, w_f + 1) if o != 0])
    p = t[:, None] + off[None, :]
    ok = (p >= 0) & (p < lengths[s][:, None])
    ctx = tokens[s[:, None], np.clip(p, 0, L - 1)]
    outs = np.concatenate([tokens[s, t][:, None], negs[s, t]], axis=1)
    return ctx.astype(np.int32), ok, outs.astype(np.int32)


def sgns_loss(w_in, w_out, index, mm=mm_f32, chunk: int = 65536) -> float:
    """Mean SGNS loss per window of a batch at the given tables."""
    ctx, ok, outs = index
    w_in, w_out = jnp.asarray(w_in), jnp.asarray(w_out)
    total = 0.0
    for i in range(0, ctx.shape[0], chunk):
        j = min(ctx.shape[0], i + chunk)
        pad = chunk - (j - i)
        sl = [np.pad(a[i:j], ((0, pad), (0, 0))) for a in (ctx, ok, outs)]
        total += float(_loss_chunk(w_in, w_out, *map(jnp.asarray, sl), mm))
    return total / max(1, ctx.shape[0])


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2))
def serve_rows(key, vocab: int, dim: int):
    """The rows handed to the server: word2vec-initialised, as a trained
    table's input rows start."""
    return (jax.random.uniform(key, (vocab, dim), jnp.float32) - 0.5) / dim


@jax.jit
def normalise(w):
    return w / jnp.maximum(jnp.linalg.norm(w, axis=1, keepdims=True), 1e-12)


def serve_table(key, vocab: int, dim: int):
    """The reference's served table: the same rows, L2-normalised."""
    return normalise(serve_rows(key, vocab, dim))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _topk(table, ids, k: int, mm):
    sc = mm(table[ids], table.T)
    sc = jnp.where(jnp.arange(table.shape[0])[None, :] == ids[:, None],
                   -jnp.inf, sc)
    # lax.top_k puts the lower index first among equal scores
    top_s, top_i = jax.lax.top_k(sc, k)
    return top_i, top_s


@functools.partial(jax.jit, static_argnums=(3,))
def _pair_scores(table, ids, cand, mm):
    return jax.vmap(lambda q, c: mm(table[q][None, :], table[c].T)[0])(
        ids, cand)


def dense_topk(table, ids: np.ndarray, k: int, mm=mm_f32, chunk: int = 128):
    """Top-k neighbours of each query id (the query itself excluded),
    ranked by (score desc, id asc): ``(ids (B, k), scores (B, k))``."""
    out_i, out_s = [], []
    for i in range(0, len(ids), chunk):
        q = jnp.asarray(np.asarray(ids[i:i + chunk], np.int32))
        ti, ts = _topk(table, q, k, mm)
        out_i.append(np.asarray(ti))
        out_s.append(np.asarray(ts))
    return np.concatenate(out_i), np.concatenate(out_s)


def pair_scores(table, ids: np.ndarray, cand: np.ndarray, mm=mm_f32):
    """Score of each query ``ids[b]`` against its candidates ``cand[b]``."""
    return np.asarray(_pair_scores(table, jnp.asarray(ids, jnp.int32),
                                   jnp.asarray(cand, jnp.int32), mm))

"""Single-device bit-array kernels: fused scatter-OR insert, gather-AND query.

Parity: these are the device-side replacement for the reference hot path —
``SETBIT pos 1`` per position on insert, ``GETBIT`` + AND on query
(BASELINE.json north_star: "inserts/queries are fused scatter-OR /
gather-AND reductions"; SURVEY.md §3.2-§3.3).

Design notes (TPU/XLA-first):

* The filter is a packed ``uint32[n_words]`` array resident in HBM; bit
  ``pos`` is ``words[pos >> 5] & (1 << (pos & 31))``.
* XLA's scatter supports add/mul/min/max combiners but **not bitwise OR**,
  and scatter-add is wrong for bits (duplicate positions carry into
  neighboring bits). The pure-XLA answer implemented here:

    1. sort (word, mask) pairs by word — ``lax.sort`` is well-tuned on TPU;
    2. segmented inclusive OR-scan (Hillis–Steele, log2 N dense vectorized
       steps) so the *last* element of each equal-word run holds the OR of
       the whole run;
    3. gather the current words, OR in the run masks, and scatter-set with
       ``unique_indices`` — losers' indices are redirected out of bounds and
       dropped, so every applied update targets a distinct word.

  Everything is dense, statically-shaped, and fuses well; there is no
  data-dependent control flow. A fused Pallas hash+scatter kernel is the
  escape hatch if this is the throughput wall (SURVEY.md §7).
* Batch padding: entries with ``valid == False`` (host pads batches to a
  static shape) are redirected to the out-of-bounds sentinel and dropped.
* Insert races are benign by construction — scatter-OR is commutative and
  idempotent (SURVEY.md §5 "Race detection").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _u32(x) -> jnp.ndarray:
    return jnp.asarray(x, jnp.uint32)


def segmented_scan_last(
    keys: jnp.ndarray, vals: jnp.ndarray, op
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Inclusive segmented scan over runs of equal (sorted) keys.

    ``vals`` may be 1-D ``[N]`` (flat scatter-OR path) or N-D ``[N, ...]``
    (blocked layout: one mask row per key) — trailing dims are combined
    elementwise within each run.

    Returns ``(scanned_vals, is_last)`` where ``scanned_vals[i]`` combines all
    ``vals[j]`` with ``j <= i`` in i's run, and ``is_last[i]`` marks the final
    element of each run (which therefore holds the full-run reduction).

    Hillis–Steele with log2(N) dense steps — each step is a shift + compare +
    select, all vectorizable on the VPU; no scatter, no dynamic shapes.
    """
    n = keys.shape[0]
    shift = 1
    while shift < n:
        prev_keys = jnp.concatenate([jnp.full((shift,), -1, keys.dtype), keys[:-shift]])
        prev_vals = jnp.concatenate(
            [jnp.zeros((shift,) + vals.shape[1:], vals.dtype), vals[:-shift]]
        )
        same = prev_keys == keys
        same = same.reshape(same.shape + (1,) * (vals.ndim - 1))
        vals = jnp.where(same, op(vals, prev_vals), vals)
        shift *= 2
    is_last = jnp.concatenate([keys[:-1] != keys[1:], jnp.ones((1,), bool)])
    return vals, is_last


def scatter_or(
    bits: jnp.ndarray, word_idx: jnp.ndarray, bit: jnp.ndarray, valid: jnp.ndarray
) -> jnp.ndarray:
    """OR ``1 << bit`` into ``bits[word_idx]`` for every valid entry.

    Args:
      bits: ``uint32[n_words]`` packed filter.
      word_idx: ``int32[N]`` word indices (flattened batch × k).
      bit: ``uint32[N]`` bit offsets in [0, 32).
      valid: ``bool[N]`` — False entries (batch padding) are dropped.

    Returns the updated ``bits`` (functionally; jit callers donate the input).
    """
    n_words = bits.shape[0]
    masks = _u32(1) << bit
    w = jnp.where(valid, word_idx, n_words).astype(jnp.int32)
    w, masks = lax.sort((w, masks), num_keys=1)
    masks, is_last = segmented_scan_last(w, masks, jnp.bitwise_or)
    target = jnp.where(is_last & (w < n_words), w, n_words)
    current = bits[jnp.minimum(w, n_words - 1)]
    merged = current | masks
    return bits.at[target].set(merged, mode="drop", unique_indices=True)


def gather_test(
    bits: jnp.ndarray, word_idx: jnp.ndarray, bit: jnp.ndarray
) -> jnp.ndarray:
    """Gather the addressed bits: returns ``uint32`` 0/1 per entry."""
    vals = bits[word_idx]
    return (vals >> bit) & _u32(1)


def query_membership(
    bits: jnp.ndarray, word_idx: jnp.ndarray, bit: jnp.ndarray
) -> jnp.ndarray:
    """AND-reduce the k bits of each key: ``bool[B]`` membership.

    ``word_idx``/``bit`` are ``[B, k]``. No short-circuit on the first zero
    bit — SIMD computes all k and reduces (SURVEY.md §3.3: the batched path
    deliberately drops the reference's scalar short-circuit).
    """
    hits = gather_test(bits, word_idx, bit)
    return jnp.all(hits == 1, axis=-1)


def popcount_fill(bits: jnp.ndarray, m: int) -> jnp.ndarray:
    """Fraction of set bits — drives estimated-FPR observability
    (fill^k ~ predicted FPR; SURVEY.md §5 metrics)."""
    set_bits = jnp.sum(jax.lax.population_count(bits).astype(jnp.float32))
    # m as a float: an int m >= 2^31 overflows JAX's int32 argument
    return set_bits / float(m)

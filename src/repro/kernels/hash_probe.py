"""Batched MICA bucket probe as a Pallas TPU kernel — the one-sided lookup
hot path (`remote_read` + `lookup_end`) fused on-chip.

TPU-native structure: the bucket indices are SCALAR-PREFETCHED and consumed
by the arena BlockSpec index_map, so the sequential grid streams exactly the
bucket lines the keys hash to (the NIC's gather, expressed as data-dependent
block fetching).  One grid step = one key: load the bucket's slots, compare
key / version-parity / lock, select the value.

Layout contract: the arena's slot region starts at word 0 (hashtable
build_layout registers "slots" first) and buckets are bucket_width slots of
SLOT_WORDS words.

Mosaic tiling: the arena is viewed lane-dense as rows of 128 words (four
slots), grouped so that one block holds exactly one bucket's rows; the
block's last two dims equal the view's, so no slot is padded to a tile and a
width-1 probe fetches one 512-byte row.  Inside the kernel each slot is
rotated to lanes 0..31 (a dynamic lane rotate when buckets are narrower than
a row), slots are compared as whole lines, the first match is picked by an
unrolled select over the static bucket width, and words are int32 (uint32
bitcast).  The output is one (1, SLOT_WORDS) line per key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import slots as sl

LANES = 128

# reply words: [found, version, value...]
REPLY_WORDS = 2 + sl.VALUE_WORDS


def _kernel(bucket_idx_ref, key_lo_ref, key_hi_ref, bucket_ref, out_ref, *,
            width: int):
    b = pl.program_id(0)
    key_lo = key_lo_ref[b]
    key_hi = key_hi_ref[b]
    line = width * sl.SLOT_WORDS
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    # word offset of the bucket inside its block (0 unless buckets are
    # narrower than a row, in which case the block is that one row)
    base = (bucket_idx_ref[b] * line) % LANES if line < LANES else 0
    rows = []
    for i in range(width):
        w0 = i * sl.SLOT_WORDS
        row = bucket_ref[w0 // LANES:w0 // LANES + 1, :]     # (1, LANES)
        # rotate slot i to lanes 0..SLOT_WORDS-1 (jnp.roll semantics)
        rows.append(pltpu.roll(row, (LANES - base - w0 % LANES) % LANES, 1))

    def matches(r):
        # per-word predicate of lookup_end's validity test, reduced over the
        # line: key match, even version (stable), lock free
        bad = (((lane == sl.KEY_LO) & (r != key_lo))
               | ((lane == sl.KEY_HI) & (r != key_hi))
               | ((lane == sl.VERSION) & ((r & 1) != 0))
               | ((lane == sl.LOCK) & (r != 0)))
        return jnp.max(bad.astype(jnp.int32), axis=1, keepdims=True) == 0

    oks = [matches(r) for r in rows]
    # first matching slot; slot 0 when none matches (its version is reported)
    slot = rows[0]
    for r, ok in zip(rows[::-1], oks[::-1]):
        slot = jnp.where(ok, r, slot)
    found = oks[0]
    for ok in oks[1:]:
        found |= ok
    # the line goes out in slot layout with the lock word (always 0 on a
    # match) carrying the found flag; the wrapper slices it into the reply
    out = jnp.where((lane >= sl.VALUE0) & ~found, 0, slot)
    out = jnp.where(lane == sl.LOCK, found.astype(jnp.int32), out)
    out_ref[...] = out[:, :sl.SLOT_WORDS]


@functools.partial(jax.jit,
                   static_argnames=("width", "interpret"))
def hash_probe(arena, bucket_idx, key_lo, key_hi, *, width: int,
               interpret: bool = False):
    """arena: (n_words,) uint32 with slots at word 0; bucket_idx: (B,) int32;
    key_lo/key_hi: (B,) uint32.  Returns (B, REPLY_WORDS) uint32."""
    B = bucket_idx.shape[0]
    line = width * sl.SLOT_WORDS
    assert line % LANES == 0 or LANES % line == 0, width
    rpb = max(1, line // LANES)         # rows per block: one bucket's rows
    # whole blocks only: the bucket region (a power-of-two bucket count)
    # always ends on a block boundary, so every real bucket is inside
    n_words = arena.shape[0] // (LANES * rpb) * (LANES * rpb)
    as_i32 = lambda x: lax.bitcast_convert_type(x.astype(jnp.uint32), jnp.int32)
    arena3d = as_i32(arena[:n_words]).reshape(-1, rpb, LANES)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((pl.Squeezed(), rpb, LANES),
                         lambda b, bidx, klo, khi:
                             (bidx[b] * line // (LANES * rpb), 0, 0)),
        ],
        out_specs=pl.BlockSpec((pl.Squeezed(), 1, sl.SLOT_WORDS),
                               lambda b, *_: (b, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, width=width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, sl.SLOT_WORDS), jnp.int32),
        interpret=interpret,
    )(bucket_idx.astype(jnp.int32), as_i32(key_lo), as_i32(key_hi), arena3d)
    out = lax.bitcast_convert_type(out[:, 0], jnp.uint32)
    return jnp.concatenate(
        [out[:, sl.LOCK:sl.LOCK + 1], out[:, sl.VERSION:sl.VERSION + 1],
         out[:, sl.VALUE0:]], axis=1)

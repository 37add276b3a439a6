"""Slot-batched decode-state surgery for continuous batching + speculation.

The decode state is a pytree whose leaves carry the batch dimension at
different positions (stacked-layer leaves have leading (n_periods, ...)
axes). ``update_slots`` scatter-writes k new-request states into k slots of
the engine's live state, leaf by leaf, locating the batch axis the same way
launch/specs.py does for shardings.

``snapshot_recurrent`` / ``rollback_state`` are the speculative-decoding
surgery: a verify pass advances the state by the whole proposed block, and
the rejected tail must be truncated per slot. KV-cache leaves (k / v /
c_kv / k_rope) are positional — entries beyond ``positions`` are never
attended (the decode mask is ``kpos <= positions``) and are overwritten in
place when decoding resumes — so their rollback is just the positions
rewind. Recurrent leaves (conv / ssm / xLSTM cell states) have no
positional identity; they are snapshotted per verify step and re-selected
at the per-slot accepted length.

``gate_state`` is the chunked-prefill counterpart: a chunk wave unrolls C
decode steps over rows with ragged valid lengths, and a row past its
length must not advance — recurrent leaves / positions / last_tokens are
re-selected per row, while KV leaves keep the new buffers (the invalid
step's garbage write landed at the un-advanced ``positions[b]`` and is
overwritten by the next real write at that index before it is ever
attended — the same masking argument as speculative rollback).

``extract_prefix`` / ``restore_prefix`` are block-granular KV restore at
an arbitrary prefill offset: one slot's state is pulled to the host with
its KV leaves sliced to the first ``length`` positions (the prefix-cache
snapshot), and restored later — possibly on another replica — by padding
the KV axis back to decode capacity and scatter-writing the batch-1 tree
over a free slot (``update_slots``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# KV-cache leaves: positional, masked by `positions`, rolled back for free.
KV_KEYS = frozenset({"k", "v", "c_kv", "k_rope"})

# suffix logical axes per leaf name; batch position = ndim - len(axes) + idx
_STATE_AXES = {
    "k": ("batch", "kv_seq", "kv_heads", None),
    "v": ("batch", "kv_seq", "kv_heads", None),
    "c_kv": ("batch", "kv_seq", None),
    "k_rope": ("batch", "kv_seq", None),
    "conv": ("batch", None, "ffn"),
    "ssm": ("batch", "ffn", None),
    "C": ("batch", "heads", None, None),
    "n": ("batch", "heads", None),
    "m": ("batch", "heads"),
    "c": ("batch", "heads", None),
    "h": ("batch", "heads", None),
    "positions": ("batch",),
    "last_tokens": ("batch", None),
}


def _leaf_key(path) -> str | None:
    for p in reversed(path):
        k = getattr(p, "key", None)
        if isinstance(k, str):
            return k
    return None


def batch_axis(path, leaf) -> int:
    key = _leaf_key(path)
    axes = _STATE_AXES.get(key)
    if axes is None or "batch" not in axes:
        raise ValueError(f"unknown state leaf {key!r} (path={path})")
    return leaf.ndim - len(axes) + axes.index("batch")


def update_slots(state, new_state, slots: jax.Array):
    """Write new_state (batch k) into ``state`` (batch B) at ``slots`` (k,).

    KV leaves of ``new_state`` may be shorter than the decode capacity (an
    unpadded prefill): they land at positions ``[0, S)`` and the slot's
    older entries past ``S`` stay, masked by ``positions`` until decoding
    overwrites them. Indexing the batch axis in place (no transpose)
    keeps the scatter from copying the whole cache."""

    def one(path, leaf, new_leaf):
        if leaf is None:
            return None
        at = [slice(None)] * leaf.ndim
        at[batch_axis(path, leaf)] = slots
        sq = _seq_axis(path, leaf)
        if sq is not None:
            at[sq] = slice(0, new_leaf.shape[sq])
        return leaf.at[tuple(at)].set(new_leaf.astype(leaf.dtype))

    return jax.tree_util.tree_map_with_path(one, state, new_state)


def select_slots(state, slots: jax.Array):
    """Read the sub-state of ``slots`` (gather along each leaf's batch axis)."""

    def one(path, leaf):
        if leaf is None:
            return None
        ax = batch_axis(path, leaf)
        return jnp.moveaxis(jnp.moveaxis(leaf, ax, 0)[slots], 0, ax)

    return jax.tree_util.tree_map_with_path(one, state)


def gate_state(valid: jax.Array, new_state, old_state):
    """Per-row validity gate for one unrolled chunk-prefill step.

    ``valid (B,)`` bool: rows that really consumed this step's token keep
    ``new_state``; exhausted rows keep ``old_state`` for recurrent leaves,
    positions and last_tokens. KV leaves always keep the new buffers —
    see the module docstring for why the invalid rows' garbage writes are
    unreachable."""

    def one(path, new_leaf, old_leaf):
        if new_leaf is None:
            return None
        if _leaf_key(path) in KV_KEYS:
            return new_leaf
        ax = batch_axis(path, new_leaf)
        shape = [1] * new_leaf.ndim
        shape[ax] = valid.shape[0]
        return jnp.where(valid.reshape(shape), new_leaf, old_leaf)

    return jax.tree_util.tree_map_with_path(one, new_state, old_state)


def _seq_axis(path, leaf):
    """KV-sequence axis of a leaf, or None for non-positional leaves."""
    axes = _STATE_AXES.get(_leaf_key(path))
    if axes is None or "kv_seq" not in axes:
        return None
    return leaf.ndim - len(axes) + axes.index("kv_seq")


def extract_prefix(state, slot: int, length: int):
    """Host snapshot of one slot's state at prefill offset ``length``:
    batch-1 numpy tree with KV leaves sliced to ``[:length]`` positions.
    Returns ``(snapshot, nbytes)`` — the byte count is what a prefix-cache
    spill/fetch transfers over the pool link."""
    nbytes = 0

    def one(path, leaf):
        nonlocal nbytes
        if leaf is None:
            return None
        ax = batch_axis(path, leaf)
        sub = jnp.moveaxis(jnp.moveaxis(leaf, ax, 0)[slot:slot + 1], 0, ax)
        sq = _seq_axis(path, sub)
        if sq is not None:
            sub = jnp.moveaxis(jnp.moveaxis(sub, sq, 0)[:length], 0, sq)
        arr = np.asarray(sub)
        nbytes += arr.nbytes
        return arr

    return jax.tree_util.tree_map_with_path(one, state), nbytes


def restore_prefix(snapshot, max_len: int):
    """Device tree from an ``extract_prefix`` snapshot: KV leaves padded
    back out to ``max_len`` decode capacity (positions beyond the prefix
    are masked by ``positions`` until overwritten), ready for
    ``update_slots`` into a free slot."""

    def one(path, leaf):
        if leaf is None:
            return None
        sq = _seq_axis(path, leaf)
        if sq is not None and leaf.shape[sq] < max_len:
            pad = [(0, 0)] * leaf.ndim
            pad[sq] = (0, max_len - leaf.shape[sq])
            leaf = np.pad(leaf, pad)
        return jnp.asarray(leaf)

    return jax.tree_util.tree_map_with_path(one, snapshot)


# ---------------------------------------------------------------------------
# speculative-decoding rollback
# ---------------------------------------------------------------------------

def snapshot_recurrent(state):
    """Cheap per-step snapshot for speculative rollback: keep recurrent
    leaves (plus positions / last_tokens), replace positional KV leaves by
    0-d placeholders so the tree structure — and thus ``tree_map`` over
    (final_state, *snapshots) — stays intact without retaining m copies of
    the KV cache."""

    def one(path, leaf):
        if leaf is None:
            return None
        if _leaf_key(path) in KV_KEYS:
            return jnp.zeros((), leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(one, state)


def rollback_state(final_state, snapshots, n_keep: jax.Array):
    """Truncate rejected speculation per slot.

    ``final_state``: state after the full m-step verify pass.
    ``snapshots``: list of m+1 ``snapshot_recurrent`` trees, where
    ``snapshots[s]`` is the state after s verify steps (s=0 = pre-verify).
    ``n_keep (B,)``: verify steps to keep per slot, in [0, m].

    Recurrent leaves (and positions / last_tokens) are re-selected at
    ``snapshots[n_keep[b]]`` per slot; KV leaves keep the final buffers —
    rows beyond the rewound ``positions`` are masked and will be
    overwritten in place by subsequent decode writes.
    """
    sel = jnp.asarray(n_keep, jnp.int32)

    def one(path, leaf_final, *snap_leaves):
        if leaf_final is None:
            return None
        if _leaf_key(path) in KV_KEYS:
            return leaf_final
        ax = batch_axis(path, leaf_final)
        stacked = jnp.stack(snap_leaves)              # (m+1, ...)
        moved = jnp.moveaxis(stacked, ax + 1, 1)      # (m+1, B, ...)
        picked = moved[sel, jnp.arange(sel.shape[0])]
        return jnp.moveaxis(picked, 0, ax)

    return jax.tree_util.tree_map_with_path(one, final_state, *snapshots)

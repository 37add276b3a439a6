"""Random model weights made from the run's seed by a counter-based hash.

Every weight is a pure function of (seed, the weight's logical name, its
flat index), so the program's parameters can be made on the device in one
jitted call, and the plain reference can make the same values again, one
layer or one set of table rows at a time, without taking anything the
program made. Values are uniform with scale ``1/sqrt(fan_in)`` (1.0 for
the embedding, norm scales 1), rounded once to the served dtype. The
fan-in is a matrix's first axis, and for a leaf stacked over experts
(``w_gu`` ``(E, d, 2F)``, ``w_down`` ``(E, F, d)``) that of one expert's
matrix.

Logical names: ``embed.w``, ``head.w``, ``final_norm.scale``,
``layer{i}.{ln1|ln2}.scale``, ``layer{i}.mixer.{wq|wk|wv|wo}``,
``layer{i}.mixer.{wdq|wuq|wdkv|wuk|wuv}`` (MLA),
``layer{i}.ffn.{gate|up|down}``, ``layer{i}.ffn.{router|w_gu|w_down}``,
``layer{i}.ffn.shared.{gate|up|down}`` (expert layers),
``engram{j}.{tables|proj|gate}``, ``engram{j}.norm.scale``.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

_C1, _C2 = np.uint32(0x85EBCA6B), np.uint32(0xC2B2AE35)
_GOLD = np.uint32(0x9E3779B9)
# leaves stacked over experts on their first axis
EXPERT_STACKS = (".ffn.w_gu", ".ffn.w_down")


def _fmix32(x):
    """murmur3's 32-bit finalizer (a bijection of uint32)."""
    x = x ^ (x >> np.uint32(16))
    x = x * _C1
    x = x ^ (x >> np.uint32(13))
    x = x * _C2
    return x ^ (x >> np.uint32(16))


def seed_words(seed: int) -> np.ndarray:
    """A non-negative seed of any size as two uint32 words."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                    np.uint32)


def name_id(name: str) -> np.uint32:
    return np.uint32(zlib.crc32(name.encode()))


def leaf_key(words, nid):
    """Per-leaf key from the seed words and a name id (uint32 arrays)."""
    return _fmix32(_fmix32(words[0] ^ nid) ^ (words[1] * _GOLD + _C2))


def uniform(key, idx, std: float):
    """Uniform values of standard deviation ``std`` at flat uint32 indices
    ``idx`` of the leaf whose key is ``key`` (float32, one rounding)."""
    h = _fmix32((idx * _GOLD) ^ key)
    h = _fmix32(h + key)
    centred = (h >> np.uint32(8)).astype(jnp.int32) - (1 << 23)
    return centred.astype(jnp.float32) * np.float32(
        std * math.sqrt(12.0) / (1 << 24))


def std_for(name: str, shape) -> float | None:
    """Init scale of a logical weight; None for a norm scale (all ones)."""
    if name.endswith(".scale"):
        return None
    if name == "embed.w":
        return 1.0
    fan_in = shape[1] if name.endswith(EXPERT_STACKS) else shape[0]
    return 1.0 / math.sqrt(max(int(fan_in), 1))


def _flat_iota(shape):
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for ax in range(len(shape) - 1, -1, -1):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, ax) \
            * np.uint32(stride)
        stride *= shape[ax]
    return idx


def _values(words, names: list, shape, dtype, valid=None,
            stacked: bool = False):
    """Values of the logical weights ``names`` (each of ``shape``), on a
    leading axis of their own when ``stacked``. ``valid``: the logical
    shape when it is smaller than ``shape`` (lane- and row-padded Engram
    tables): the flat index counts the logical shape and the padding is
    zeros."""
    shape = tuple(int(s) for s in shape)
    valid = shape if valid is None else tuple(int(s) for s in valid)
    std = std_for(names[0], valid)
    lead = (len(names),) if stacked else ()
    if std is None:
        return jnp.ones(lead + shape, dtype)
    if math.prod(valid) >= 2 ** 32:
        raise ValueError(f"{names[0]}: {valid} overflows the index")
    ids = np.array([name_id(n) for n in names], np.uint32)
    key = leaf_key(jnp.asarray(words, jnp.uint32), jnp.asarray(ids))
    key = key.reshape(lead + (1,) * len(shape)) if lead else key[0]
    vals = uniform(key, _flat_iota(valid), std).astype(dtype)
    if valid != shape:
        vals = jnp.pad(vals, [(0, 0)] * len(lead)
                       + [(0, a - b) for a, b in zip(shape, valid)])
    return vals


def logical(words, name: str, shape, dtype="bfloat16"):
    """The logical weight ``name`` of ``shape`` in ``dtype`` (traceable)."""
    return _values(words, [name], shape, dtype)


def rows_of(words, name: str, rows, shape, dtype="bfloat16"):
    """Rows ``rows`` (int array of flat row ids) of the logical weight
    ``name`` seen as a ``(-1, shape[-1])`` matrix: shape
    ``rows.shape + (shape[-1],)``, the values ``logical`` puts there.
    ``shape`` is the weight's logical shape (it sets the scale)."""
    std = std_for(name, shape)
    width = int(shape[-1])
    key = leaf_key(jnp.asarray(words, jnp.uint32), name_id(name))
    idx = (rows.astype(jnp.uint32) * np.uint32(width))[..., None] \
        + jnp.arange(width, dtype=jnp.uint32)
    return uniform(key, idx, std).astype(dtype)


def by_id(words, nid, shape, name_like: str, dtype="bfloat16"):
    """``logical`` for a traced name id (one compile serves every layer):
    ``name_like`` is any name of the same kind, for the scale."""
    shape = tuple(int(s) for s in shape)
    std = std_for(name_like, shape)
    if std is None:
        return jnp.ones(shape, dtype)
    key = leaf_key(jnp.asarray(words, jnp.uint32), nid)
    return uniform(key, _flat_iota(shape), std).astype(dtype)


# ---------------------------------------------------------------- program

def _path_parts(path) -> list:
    return [getattr(k, "key", getattr(k, "idx", k)) for k in path]


def _layer_names(cfg, parts) -> tuple[list[str], bool]:
    """Logical names behind one program parameter leaf, and whether the
    leaf stacks them on a leading axis (a scanned layer stack)."""
    from repro.models.transformer import segment_plan
    if parts[0] == "segments":
        seg = segment_plan(cfg)[parts[1]]
        rest = ".".join(str(p) for p in parts[4:])
        if parts[2] == "prefix":
            return [f"layer{seg.layers[parts[3]]}.{rest}"], False
        layers = [seg.layers[seg.prefix_len + r * seg.period + parts[3]]
                  for r in range(seg.n_periods)]
        return [f"layer{i}.{rest}" for i in layers], True
    if parts[0] == "engram":
        return [f"engram{parts[2]}." + ".".join(str(p) for p in parts[3:])], \
            False
    return [".".join(str(p) for p in parts)], False


def program_params(cfg, seed: int, device):
    """The program's parameter tree for ``cfg`` (``repro`` layout), made
    on ``device`` by one jitted call from ``seed``."""
    from repro.models.model import abstract_params
    abstract = abstract_params(cfg)
    hd = cfg.engram.head_dim if cfg.engram is not None else None

    def make(words):
        def one(path, leaf):
            names, stacked = _layer_names(cfg, _path_parts(path))
            shape = leaf.shape[1:] if stacked else leaf.shape
            valid = None
            if names[0].endswith(".tables"):
                # (n_tables, table_vocab, head_dim) inside the padded leaf
                valid = (shape[0], cfg.engram.table_vocab, hd)
            return _values(words, names, shape, leaf.dtype, valid, stacked)
        return jax.tree_util.tree_map_with_path(one, abstract)

    out = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(make, out_shardings=out)(seed_words(seed))

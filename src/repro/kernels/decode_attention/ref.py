"""Pure-jnp oracle for the decode_attention kernel: the masked XLA form,
which reads every position of the layer and masks those at or past each
slot's length (``models.attention.decode_attention`` off the TPU)."""
import math

import jax
import jax.numpy as jnp

NEG_INF = -2.0 ** 30


def decode_attention_ref(q, k, v, layer, lens):
    """q (B, H_q, D); k, v (n_layers, B, S, H_kv, D); lens (B,) ->
    (B, H_q, D). A slot of length 0 has no key to attend: its row is
    whatever the mask leaves, and callers do not read it."""
    kc, vc = k[layer], v[layer]
    B, Hq, D = q.shape
    S, H = kc.shape[1], kc.shape[2]
    qg = q.reshape(B, H, Hq // H, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                   kc.astype(jnp.float32)) / math.sqrt(D)
    valid = jnp.arange(S)[None] < lens[:, None]
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(vc.dtype), vc)
    return out.reshape(B, Hq, D)

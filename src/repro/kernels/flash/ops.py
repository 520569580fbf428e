"""Public wrappers: (B, S, H, D) layout <-> kernel layout, prefill+decode."""
from __future__ import annotations

import jax.numpy as jnp

from ..stream import interpret_default
from .kernel import flash_bhsd


def flash_attention_tpu(q, k, v, *, causal: bool = True, window=None,
                        bq: int = 128, bk: int = 128):
    """q: (B, Sq, H, D); k/v: (B, Skv, Hkv, D). GQA via BlockSpec index map."""
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    g = H // Hkv
    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, D)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * Hkv, Skv, D)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * Hkv, Skv, D)
    bq_ = min(bq, max(8, Sq))
    bk_ = min(bk, max(8, Skv))
    out = flash_bhsd(qf, kf, vf, causal=causal, window=window, bq=bq_,
                     bk=bk_, g=g, interpret=interpret_default())
    return jnp.moveaxis(out.reshape(B, H, Sq, D), 1, 2)


def flash_decode_tpu(q, k_cache, v_cache, *, window=None, bk: int = 256):
    """One-token decode: q (B, 1, H, D) against (B, S, Hkv, D) caches.
    Implemented as a Sq=8 padded prefill block (only row 0 is real)."""
    B, _, H, D = q.shape
    out = flash_attention_tpu(jnp.pad(q, ((0, 0), (0, 7), (0, 0), (0, 0))),
                              k_cache, v_cache, causal=False, window=window,
                              bq=8, bk=bk)
    return out[:, :1]

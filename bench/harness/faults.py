"""Faults planted under the timed path, each of which a run has to report as
not correct: a token altered where a program produces it, and the sampler
dropping one part of a request's sampling rule.  The tests plant them at a
small size on the CPU, ``control.py --fault`` at the cell's size on the
chip.
"""
from __future__ import annotations

from typing import Callable

FAULTS = ("decode_token", "prefill_token", "top_p", "top_k", "temperature")


def _altered(real, vocab: int, decode: bool):
    def altered(*args, **kw):
        out = real(*args, **kw)
        tok = (out[0] + 1) % vocab
        if decode:
            return (tok, out[1], out[2], (out[3] + 1) % vocab)
        return (tok,) + tuple(out[1:])

    altered.lower = real.lower          # set-up compiles the real program
    return altered


def _sampler(real, name: str):
    import jax.numpy as jnp

    def faulty(logits, temps, top_k, top_p, keys):
        if name == "top_p":
            top_p = jnp.ones_like(top_p)
        elif name == "top_k":
            top_k = jnp.zeros_like(top_k)
        else:
            temps = jnp.where(temps > 0.0, 1.0, temps)
        return real(logits, temps, top_k, top_p, keys)

    return faulty


def plant(name: str, vocab: int) -> Callable[[], None]:
    """Plant fault ``name`` in the program; returns the call that removes
    it.  Traced programs are dropped both ways, so set-up traces anew."""
    import jax
    from repro.serving import engine as E
    attr = {"decode_token": "_paged_decode_block_kernel",
            "prefill_token": "_prefill_kernel"}.get(
                name, "sample_tokens_batched")
    real = getattr(E, attr)
    if name in ("decode_token", "prefill_token"):
        fake = _altered(real, vocab, name == "decode_token")
    elif name in ("top_p", "top_k", "temperature"):
        fake = _sampler(real, name)
    else:
        raise ValueError(f"unknown fault {name!r}; one of {FAULTS}")
    setattr(E, attr, fake)
    jax.clear_caches()

    def undo():
        setattr(E, attr, real)
        jax.clear_caches()
    return undo

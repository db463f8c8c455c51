"""``benchmarks/hlo_digest.py`` compares two trees' compiled programs with
the op metadata left out: a named scope must not change the digest, and a
change to what is computed must."""

from __future__ import annotations

import hashlib
from contextlib import nullcontext

import jax
import jax.numpy as jnp

from benchmarks.hlo_digest import strip


def _compiled(fn) -> str:
    x = jax.ShapeDtypeStruct((64,), jnp.float32)
    return jax.jit(fn).lower(x).compile().as_text()


def _program(scoped: bool, wave=jnp.sin):
    """One program (one name), with or without named scopes."""
    def program(x):
        with jax.named_scope("some_phase") if scoped else nullcontext():
            y = wave(x) * 2.0
        with jax.named_scope("another_phase") if scoped else nullcontext():
            return jnp.sum(y)
    return program


def _digest(text: str) -> str:
    return hashlib.sha256(strip(text).encode()).hexdigest()


def test_scope_names_leave_the_stripped_program_unchanged():
    plain = _compiled(_program(False))
    scoped = _compiled(_program(True))
    assert "some_phase" in scoped and "some_phase" not in plain
    assert "metadata=" not in strip(scoped)
    assert _digest(plain) == _digest(scoped)


def test_another_computation_changes_the_digest():
    plain = _compiled(_program(False))
    assert _digest(plain) != _digest(_compiled(_program(False, jnp.cos)))

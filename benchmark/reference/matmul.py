"""The references' contractions, at a stated precision.

``"highest"`` is float32 at ``Precision.HIGHEST``: the configuration's
precision. ``"bf16_3x"`` is the control's: the nearest precision below,
three bfloat16 passes (what ``Precision.HIGH`` does on a TPU), emulated
here so that it reads the same on any backend. Each operand is split
into ``hi + lo`` with both parts bfloat16 values; the product keeps
``hi.hi + hi.lo + lo.hi`` and drops ``lo.lo``. ``hi.hi + hi.lo`` is taken
as one pass of ``a_hi`` against ``b_hi + b_lo``, which float32 holds
exactly (16 significant bits), as does each product with an 8-bit
``a_hi``; so the passes at ``HIGHEST`` are the bfloat16 passes with
float32 accumulation, and the large operand is split once. A part is rounded to
bfloat16 (to nearest, ties to even) on the bits of its float32 word: a
float32 -> bfloat16 -> float32 round trip may be dropped by XLA, which
may keep excess precision, and then ``lo`` reads 0 and the control
becomes one bfloat16 pass.
"""

from __future__ import annotations

PRECISIONS = ("highest", "bf16_3x")


def _bf16(x):
    import jax
    import jax.numpy as jnp

    def r(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
    if jnp.iscomplexobj(x):
        return jax.lax.complex(r(jnp.real(x)), r(jnp.imag(x)))
    return r(x)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def einsum(expr: str, a, b, precision: str):
    import jax
    import jax.numpy as jnp
    highest = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.einsum(expr, a, b, precision=highest)
    if precision != "bf16_3x":
        raise ValueError(f"precision {precision!r} is not one of "
                         f"{PRECISIONS}")
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    return (jnp.einsum(expr, a_hi, b_hi + b_lo, precision=highest)
            + jnp.einsum(expr, a_lo, b_hi, precision=highest))

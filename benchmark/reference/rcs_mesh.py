"""Plain reference for the random circuits of ``families/rcs_mesh.py``,
on a state sharded over the devices the program's state lives on.

The algorithm is that of ``reference/rcs.py``, whose step list
(:func:`~benchmark.reference.rcs.plan`) it reads: the state is a
complex64 tensor with one axis of ``2^cols`` entries per grid row, every
step a contraction along one axis (``mat``), a phase over two axes
(``phase``) or a relabelling of two rows' axes (``swap``). It shares no
code with the program.

Here the tensor is held flat and sharded over the devices as the input
planes are (the top bits of the amplitude index; the planes' sharding
gives the mesh), so that no device holds more than its share and one
step's temporaries. Each step views it around the axes it touches,
``(rest, 2^cols, rest)`` for a contraction: a few large axes, where a
view of all the row axes at 30 qubits, ``(64,)*5``, took the TPU
compiler tens of GB of host memory. A contraction along the row held at
the top, whose bits name the devices, first exchanges the top two rows'
axes, an all-to-all that an explicit resharding
(``with_sharding_constraint``) asks for; neighbouring exchanges put the
rows back in their canonical order at the end.
"""

from __future__ import annotations

from . import matmul
from .rcs import plan


def make_apply(cfg: dict, precision):
    """A function: packed float32 planes ``(2, 2^n)`` sharded over a
    mesh's amplitude axis in, the final state as a complex64 vector
    ``(2^n,)`` sharded alike out."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    rows, cols = cfg["grid"]
    dim = 1 << cols
    steps = plan(cfg)
    consts = [jnp.asarray(s[-1], jnp.complex64) if s[0] != "swap" else None
              for s in steps]
    compiled = {}

    def build(mesh, name):
        def constrain(x):
            """``x`` sharded along its first axis longer than 1: the
            top bits of the amplitude index."""
            lead = next(i for i, d in enumerate(x.shape) if d > 1)
            spec = [None] * x.ndim
            spec[lead] = name
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec)))

        def view(psi, *positions):
            """The flat state as a tensor split at the axes of the given
            physical positions: ``(rest, dim, rest, dim, ..., rest)``."""
            shape, prev = [], -1
            for p in positions:
                shape += [dim ** (p - prev - 1), dim]
                prev = p
            return constrain(psi.reshape(shape + [dim ** (rows - 1 - prev)]))

        def flat(x):
            return constrain(x.reshape(-1))

        def exchange(psi, phys, p):
            """Exchange the rows at positions ``p`` and ``p + 1``; at
            ``p = 0`` their data moves between the devices (an
            all-to-all)."""
            phys[p], phys[p + 1] = phys[p + 1], phys[p]
            return flat(jnp.swapaxes(view(psi, p, p + 1), 1, 3))

        def apply(planes, consts):
            psi = flat(jax.lax.complex(planes[0], planes[1]))
            # row held at each physical position, most significant first
            phys = list(reversed(range(rows)))
            for step, const in zip(steps, consts):
                if step[0] == "mat":
                    if phys.index(step[1]) == 0:
                        psi = exchange(psi, phys, 0)
                    x = view(psi, phys.index(step[1]))
                    psi = flat(matmul.einsum("YZ,aZb->aYb", const, x,
                                             precision))
                elif step[0] == "phase":
                    pa, pb = phys.index(step[1]), phys.index(step[2])
                    t = const if pa < pb else const.T
                    x = view(psi, min(pa, pb), max(pa, pb))
                    psi = flat(x * t.reshape(1, dim, 1, dim, 1))
                else:
                    ra, rb = phys.index(step[1]), phys.index(step[2])
                    phys[ra], phys[rb] = phys[rb], phys[ra]
                psi = jax.lax.optimization_barrier(psi)
            # back to the canonical order (row r at position rows-1-r),
            # one exchange of neighbouring axes at a time
            for i in range(rows):
                for p in range(rows - 1 - i):
                    if phys[p] < phys[p + 1]:
                        psi = jax.lax.optimization_barrier(
                            exchange(psi, phys, p))
            return psi

        return jax.jit(apply)

    def jitted(planes):
        key = _mesh_axis(planes)
        if key not in compiled:
            compiled[key] = build(*key)
        return compiled[key]

    def call(planes):
        return jitted(planes)(planes, consts)

    # for a compile from shapes (``jax.ShapeDtypeStruct`` planes)
    call.lower = lambda planes: jitted(planes).lower(planes, consts)
    return call


def _mesh_axis(planes):
    """The mesh and the axis name the planes' amplitude axis is sharded
    along."""
    sharding = getattr(planes, "sharding", None)
    spec = getattr(sharding, "spec", None)
    if spec is None or len(spec) < 2 or spec[1] is None:
        raise ValueError("the sharded reference needs planes sharded along "
                         "their amplitude axis over a mesh")
    return sharding.mesh, spec[1]


def _planes_sharding(psi):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(psi.sharding.mesh, P(None, *psi.sharding.spec))


def to_planes(psi):
    """A sharded complex vector as packed float32 planes ``(2, 2^n)``,
    sharded alike."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda z: jnp.stack([jnp.real(z), jnp.imag(z)]),
                   out_shardings=_planes_sharding(psi))(psi)


def relative_error(planes, psi):
    """``||state - reference|| / ||reference||`` for packed planes
    against a sharded complex vector, on the devices. Planes on the host
    are placed under the reference's sharding first."""
    import jax
    import jax.numpy as jnp

    if not isinstance(planes, jax.Array):
        planes = jax.device_put(planes, _planes_sharding(psi))

    @jax.jit
    def err(planes, psi):
        dr = planes[0] - jnp.real(psi)
        di = planes[1] - jnp.imag(psi)
        num = jnp.sum(dr * dr) + jnp.sum(di * di)
        den = jnp.sum(jnp.real(psi) ** 2) + jnp.sum(jnp.imag(psi) ** 2)
        return jnp.sqrt(num / den)

    return float(err(planes, psi))

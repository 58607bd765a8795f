"""Jit'd public wrappers around the Pallas kernels.

On the CPU (where the tests run) the kernels execute with
interpret=True — the kernel body runs in Python for correctness
validation; BlockSpecs target TPU v5e VMEM.  On a TPU they run
compiled.  Any other platform has no Pallas TPU lowering and raises.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import bf_relax as _bf
from . import bound_dist as _bd
from . import ktrop as _kt

INF = _bf.INF


def interpret_mode() -> bool:
    """Interpret on "cpu", compile on "tpu", raise on anything else."""
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas TPU kernels run compiled on 'tpu' or interpreted on "
            f"'cpu'; the default JAX backend is {platform!r}"
        )
    return platform == "cpu"


def bf_relax_step(dist, adj, spur_onehot, banned_next, cap=None):
    """One fused masked BF relaxation (see kernels/bf_relax.py)."""
    S, J, z = dist.shape
    if cap is None:
        cap = jnp.full((S, J), INF, jnp.float32)
    return _bf.bf_relax(
        dist.astype(jnp.float32),
        adj.astype(jnp.float32),
        spur_onehot.astype(jnp.float32),
        banned_next.astype(jnp.float32),
        cap.astype(jnp.float32),
        interpret=interpret_mode(),
    )


def ktrop_relax_step(D, adj):
    """One k-distinct tropical relaxation (see kernels/ktrop.py)."""
    return _kt.ktrop_relax(
        D.astype(jnp.float32), adj.astype(jnp.float32),
        interpret=interpret_mode(),
    )


def bound_dist_blocked(w_sorted, n_sorted, cum_before, sub_blocked, phi):
    """Blocked bound-distance evaluation (see kernels/bound_dist.py)."""
    return _bd.bound_dist(
        w_sorted.astype(jnp.float32),
        n_sorted.astype(jnp.float32),
        cum_before.astype(jnp.float32),
        sub_blocked.astype(jnp.int32),
        phi.astype(jnp.float32),
        interpret=interpret_mode(),
    )

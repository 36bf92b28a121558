"""Mesh helpers for sharded GNN serving (``repro.serving``).

The serving engine row-partitions a graph over a 1-D device mesh whose
single axis is named ``"shards"``.  Two helpers cover the two execution
modes:

  * :func:`serving_mesh` — a real ``jax.make_mesh`` for the SPMD
    (``jax.shard_map``) path; requires one device per shard.  CPU-testable
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
  * :func:`shard_devices` — a round-robin device assignment for the
    per-shard launch loop; oversubscription (more shards than devices) is
    allowed there, so a laptop can exercise a 4-shard layout on 1 CPU.
"""
from __future__ import annotations

import jax

#: The one mesh axis sharded serving partitions rows over.
SHARD_AXIS = "shards"


def serving_mesh(num_shards: int):
    """1-D ``(num_shards,)`` mesh over the ``"shards"`` axis.

    Raises ``ValueError`` when fewer devices exist than shards — the SPMD
    path places exactly one shard per device.  (Force host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to test on
    CPU.)
    """
    num_shards = int(num_shards)
    avail = jax.device_count()
    if num_shards > avail:
        raise ValueError(
            f"serving_mesh({num_shards}) needs {num_shards} devices but "
            f"only {avail} exist; use the per-shard launch loop "
            "(shard_devices) or force host devices via XLA_FLAGS="
            f"--xla_force_host_platform_device_count={num_shards}")
    # Auto axis: shardings propagate as in jit, so eager indexing of a
    # sharded result (the engine's ragged-tail trim) needs no out_sharding
    return jax.make_mesh((num_shards,), (SHARD_AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def shard_devices(num_shards: int, devices=None) -> list:
    """Round-robin device per shard for the launch-loop execution mode.

    Unlike :func:`serving_mesh` this never fails on small hosts: with
    fewer devices than shards, shards share devices (and the engine's
    double-buffered dispatch degrades gracefully to plain sequencing).
    """
    devices = list(devices if devices is not None else jax.devices())
    if not devices:
        raise ValueError("no jax devices available")
    return [devices[s % len(devices)] for s in range(int(num_shards))]

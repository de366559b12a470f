"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization, and smoke tests must keep seeing 1 device.

Target hardware: TPU v5e pods. Single pod = 256 chips as a 16×16
``(data, model)`` mesh; multi-pod = 2 pods = 512 chips as
``(pod, data, model)`` — the ``pod`` axis carries the federated client
dimension of pod-level CC-FedAvg (DESIGN.md §2) and the outermost data
parallelism for plain training.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

#: the chip the production meshes model, as ``jax.Device.device_kind``
#: names it
V5E = "TPU v5 lite"

#: per-chip peaks keyed by ``device_kind``, for the roofline analysis.
#: Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s of interconnect over 4 ICI links (50 GB/s
#: per link).
PEAKS = {
    V5E: {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def chip_peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip; a kind missing from
    :data:`PEAKS` is an error, never a default."""
    if device_kind not in PEAKS:
        raise ValueError(f"no peak rates for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU tests of the sharded code paths."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_client_mesh(n_shards: int | None = None):
    """1-D ``("clients",)`` mesh for the sharded federated executor.

    The stacked client dimension of the round state is ``shard_map``'ed over
    this axis (:func:`repro.core.rounds.make_sharded_span_runner`). Defaults
    to all visible devices; pass ``n_shards`` to use a prefix of them.
    """
    n = len(jax.devices()) if n_shards is None else n_shards
    if n < 1 or n > len(jax.devices()):
        raise ValueError(f"n_shards must be in [1, {len(jax.devices())}], "
                         f"got {n}")
    return _auto_mesh((n,), ("clients",))


def make_fed_mesh(axes: tuple[str, ...] = ("clients", "model"),
                 shape: tuple[int, ...] | None = None):
    """2-D federated mesh composing the client axis with model-axis tensor
    sharding.

    The sharded executor ``shard_map``'s the stacked client dimension over
    ``"clients"`` exactly as on the 1-D mesh (specs that never name
    ``"model"`` are simply replicated over it), while
    :func:`repro.sharding.rules.params_pspecs` with
    :func:`repro.sharding.rules.make_fed_rules` places the rank dim of
    stacked per-client LoRA adapters — logical axis ``"lora"`` — on
    ``"model"``. Default shape puts every visible device on the clients
    axis; pass e.g. ``shape=(2, 2)`` on a 4-device host for a genuinely
    2-D layout.
    """
    if "clients" not in axes:
        raise ValueError(f"a federated mesh needs a 'clients' axis, "
                         f"got {axes}")
    ndev = len(jax.devices())
    if shape is None:
        shape = tuple(ndev if a == "clients" else 1 for a in axes)
    if len(shape) != len(axes):
        raise ValueError(f"shape {shape} does not match axes {axes}")
    n = 1
    for s in shape:
        n *= s
    if n < 1 or n > ndev:
        raise ValueError(f"mesh size {n} must be in [1, {ndev}]")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    """A mesh whose axes XLA partitions automatically (``AxisType.Auto``):
    the executors place data with ``shard_map`` specs and
    ``with_sharding_constraint`` and leave the rest to the partitioner.
    ``jax.make_mesh`` defaults to explicit axes, under which those
    constraints are refused and the cohort scatters of the sharded
    executor would need an ``out_sharding`` at every ``.at[].set``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def best_client_shards(cohort_size: int, max_shards: int | None = None) -> int:
    """Largest device count ≤ ``max_shards`` that divides the cohort —
    ``shard_map`` needs the cohort split evenly, so e.g. a 6-client cohort
    on a 4-device host uses 3 shards rather than failing."""
    limit = min(cohort_size, max_shards or len(jax.devices()))
    return max(d for d in range(1, limit + 1) if cohort_size % d == 0)


def make_edge_mesh(n_shards: int | None = None):
    """1-D ``("edges",)`` mesh for the hierarchical two-tier executor.

    Edge aggregators — and with them their member clients — are split over
    this axis (:func:`repro.core.rounds.make_hierarchical_span_runner`):
    intra-edge rounds run entirely shard-local, and only the edge→server
    sync rounds communicate across it. Defaults to all visible devices;
    pass ``n_shards`` to use a prefix of them.
    """
    n = len(jax.devices()) if n_shards is None else n_shards
    if n < 1 or n > len(jax.devices()):
        raise ValueError(f"n_shards must be in [1, {len(jax.devices())}], "
                         f"got {n}")
    return _auto_mesh((n,), ("edges",))


def best_edge_shards(n_edges: int, max_shards: int | None = None) -> int:
    """Largest device count ≤ ``max_shards`` that divides the edge count —
    whole edges must land on one device so intra-edge aggregation never
    crosses shards."""
    limit = min(n_edges, max_shards or len(jax.devices()))
    return max(d for d in range(1, limit + 1) if n_edges % d == 0)

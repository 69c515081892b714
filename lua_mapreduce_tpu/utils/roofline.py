"""Roofline accounting: chip peak FLOP/s and MFU.

The reference ships no MFU notion — its perf story is wall-clock tables
(/root/reference/README.md:43-113). The build's north star is stated as
an MFU target (BASELINE.md: "≥50% MFU on the digits model"), so model
FLOP helpers (``models/*/flops_per_example``) need a denominator: the
chip's peak matmul FLOP/s. Known TPU generations are in a table (public
per-chip bf16 figures, e.g. jax-ml.github.io/scaling-book). A device
that is not in the table is an error, not a default: a utilization
against a guessed peak is not a device metric.
"""

from __future__ import annotations

# Per-chip peak dense bf16 matmul FLOP/s, keyed by jax Device.device_kind.
PEAK_BF16_FLOPS = {
    "TPU v2": 46e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,     # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,          # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,     # Trillium / v6e
    "TPU v6e": 918e12,
}

# Per-chip HBM bandwidth (bytes/s), keyed like PEAK_BF16_FLOPS. Public
# figures (jax-ml.github.io/scaling-book hardware table). Used as the
# memory-roofline denominator for FLOP-less ops: an op cannot finish
# faster than reading its inputs once at this rate.
PEAK_HBM_BYTES = {
    "TPU v2": 700e9,
    "TPU v3": 900e9,
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,      # v5e
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,          # v5p
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,     # Trillium / v6e
    "TPU v6e": 1640e9,
}


def _peak(table: dict, what: str, device) -> float:
    import jax
    if device is None:
        device = jax.devices()[0]
    kind = device.device_kind
    if kind not in table:
        raise ValueError(
            f"no {what} on record for device_kind {kind!r} "
            f"(known: {sorted(table)}); add it to utils/roofline.py "
            f"with its source")
    return table[kind]


def peak_hbm_bytes_per_s(device=None) -> float:
    """Peak HBM bandwidth (bytes/s) for one chip, from the table;
    raises ``ValueError`` for a ``device_kind`` that is not in it."""
    return _peak(PEAK_HBM_BYTES, "peak HBM bandwidth", device)


def peak_flops_per_s(device=None) -> float:
    """Peak dense bf16 FLOP/s for one chip, from the table; raises
    ``ValueError`` for a ``device_kind`` that is not in it."""
    return _peak(PEAK_BF16_FLOPS, "peak bf16 FLOP/s", device)


def best_time(fn, reps: int = 3) -> float:
    """Best wall time of ``fn()`` over ``reps`` calls. ``fn`` must wait
    for its own result (``jax.block_until_ready`` or a device→host
    fetch): dispatch is asynchronous, and a timing without the wait
    measures the enqueue."""
    import time

    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def mfu(model_flops: float, seconds: float, n_chips: int = 1,
        device=None) -> float:
    """Model FLOP utilization in [0,1]: counted model FLOPs per second
    as a fraction of ``n_chips`` × chip peak."""
    return model_flops / seconds / (n_chips * peak_flops_per_s(device))

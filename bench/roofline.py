"""Chip peaks and the CC tick's least work, for roofline shares.

Peaks are keyed by the ``device_kind`` JAX reports.  A device that is not in
the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
    # 819 GB/s per chip.
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"},
}

# What one CC tick reads and writes per live flow, 4 bytes a word, whatever
# implements it: the congestion-control state (FlowCCState, 12 words) and
# Algorithm 1's iteration tracker (IterDetectState, 6 words) in and out, the
# feedback (acks, loss, CNP, time) and the flow's bytes per iteration in,
# and the send rate out.
CC_STATE_WORDS = 12
ITER_STATE_WORDS = 6
FEEDBACK_WORDS = 4
WORDS_PER_FLOW = 2 * (CC_STATE_WORDS + ITER_STATE_WORDS) + FEEDBACK_WORDS + 1 + 1
BYTES_PER_WORD = 4

# Arithmetic of one Reno/MLTCP tick per flow: Algorithm 1 (ack bytes, byte
# counter, job sum, gap, running max, boundary test, gap EWMA, ratio with its
# clamp and reciprocal) 14, F = S*x + I 2, Reno (increase step, add, cut,
# clamps, cooldown) 8, and the rate out 1.
FLOPS_PER_FLOW = 25


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; known: "
                         f"{sorted(PEAKS)}") from None


def _counted(config: dict) -> None:
    """The counts above are Reno's with MLTCP; any other transport has
    none yet."""
    if config["transport"]["algo"] != "reno":
        raise ValueError(f"no CC-tick count for transport "
                         f"{config['transport']['algo']!r}; counted: reno")


def live_flows(config: dict) -> int:
    return int(config["jobs"]["n"]) * int(config["transport"]["sockets_per_job"])


def cc_tick_bytes(config: dict, points: int) -> int:
    """Bytes one CC-tick call moves for ``points`` sweep points at once."""
    _counted(config)
    return WORDS_PER_FLOW * BYTES_PER_WORD * live_flows(config) * points


def cc_tick_flops(config: dict, points: int) -> int:
    _counted(config)
    return FLOPS_PER_FLOW * live_flows(config) * points


def least_seconds(config: dict, points: int, device_kind: str) -> tuple[float, str]:
    """The least time the chip could take for one call, and which of the two
    bounds sets it."""
    p = peaks(device_kind)
    by_bytes = cc_tick_bytes(config, points) / p["bytes_per_s"]
    by_flops = cc_tick_flops(config, points) / p["flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")

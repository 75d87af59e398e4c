"""kernel.hbm_share: the delayed window kernel's share of the chip's HBM
bandwidth, in percent: the logical bytes of every kernel call in the traced
window (``bench/kernel_bytes.py``) over the peak bandwidth
(``bench/peaks.json``), over the kernel's device time in the trace."""
import json
from pathlib import Path

from bench.kernel_bytes import KERNEL_EVENT

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def read(ctx):
    seconds = ctx["trace"].op_seconds(KERNEL_EVENT)
    if not seconds:
        return None
    devices = json.loads(PEAKS.read_text())["devices"]
    kind = ctx["device_kind"]
    if kind not in devices:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}")
    run = ctx["run"]
    moved = run.kernel_bytes_per_call * run.attempted
    return 100.0 * moved / devices[kind]["hbm_bytes_per_s"] / seconds

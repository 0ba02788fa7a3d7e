"""The exchange's share of its roofline, in percent: the bytes each chip
sends off itself per epoch (``exchange.wire_bytes`` of the program's
shapes, padding included) times the epochs in the window, over the
busiest chip's exchange seconds from start to done
(``exchange.exchange_s``), over the chip's interconnect peak
(``ici_bits_per_s`` / 8 of ``peaks.json``)."""
import json
from pathlib import Path

from benchmarks.chip.exchange import exchange_s

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def read(run):
    per_dev = exchange_s(run.trace)
    if per_dev is None or not run.stats.get("wire_bytes_per_epoch"):
        return None
    peak = json.loads(PEAKS.read_text())["devices"][
        run.stats["device_kind"]]["ici_bits_per_s"] / 8
    moved = run.stats["wire_bytes_per_epoch"] * run.stats["epochs"]
    return 100.0 * moved / max(per_dev.values()) / peak

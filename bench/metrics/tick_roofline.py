"""tick_roofline: the share of the HBM roofline the query step reached
over the traced window, in %: the window's ticks times the bytes a tick
has to read (`bench/bytes.py`, from the configuration's unpadded
shapes), at the device's peak bandwidth (`bench/peaks.json`), over the
device's busy time in the window."""
from bench.bytes import tick_bytes


def read(window):
    ticks = [t for t in window.ticks if t.batch > 0]
    busy = window.trace["busy_s"]
    if not ticks or busy <= 0:
        return None
    cfg = window.config
    need = sum(tick_bytes(cfg["n_users"], cfg["d"], cfg["tau"],
                          cfg["storage"], t.batch) for t in ticks)
    return 100.0 * need / window.peaks["hbm_bytes_per_s"] / busy

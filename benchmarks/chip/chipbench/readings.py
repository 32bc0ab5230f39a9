"""Readings of a traced window, shared by the per-layer metric files."""


def idle_share(rec):
    """Per cent of the window in which no operation ran on the device."""
    t = rec.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]), "%"


def search_device_ms(rec):
    """Device time of the search program per execution, in ms."""
    t = rec.get("trace")
    if not t or t["program_n"] == 0:
        return None
    return 1e3 * t["program_s"] / t["program_n"], "ms"


def host_ms_per_batch(rec):
    """Host wall time of a batch (assembly to result on the host) less the
    device time of its search, per batch, in ms."""
    t = rec.get("trace")
    if not t or t["n_batches"] == 0 or t["program_n"] == 0:
        return None
    return 1e3 * (t["batch_host_s"] - t["program_s"]) / t["n_batches"], "ms"


def search_roofline(rec):
    """Least time the chip could take for the search's bytes and FLOPs, over
    the search program's device time, in per cent."""
    t, need, peaks = rec.get("trace"), rec.get("search_bytes"), rec.get("peaks")
    if not t or not need or not peaks or t["program_s"] <= 0:
        return None
    least = max(need["bytes"] / peaks["hbm_Bps"],
                need["flops"] / peaks["bf16_flops"])
    return 100.0 * least / t["program_s"], "%"


def scan_roofline(rec):
    """Least time the chip could take to read the real rows of the probed
    lists at its HBM peak, over the scan stage's device time, both per
    batch, in per cent."""
    t, need, peaks = rec.get("trace"), rec.get("search_bytes"), rec.get("peaks")
    found = t and t.get("stages")
    if not found or not need or not peaks or not rec.get("batches"):
        return None
    scan_s = found["seconds"]["scan"] / found["executions"]
    if scan_s <= 0:
        return None
    least = need["row_bytes"] / len(rec["batches"]) / peaks["hbm_Bps"]
    return 100.0 * least / scan_s, "%"

"""The Pallas ``hash_partition`` kernel's share of its roofline: the least
time its bytes need at the chip's HBM bandwidth (words in, bucket ids and
the per-lane histogram out), over its ops' device time per call (device
trace). Bandwidth bounds it."""


def read(record, trace):
    if trace is None or not trace.units() or "kernel_bytes_per_unit" not in record:
        return None
    seconds = trace.op_seconds(lambda name: name.startswith("hash_partition"))
    if seconds <= 0:
        return None
    least = record["kernel_bytes_per_unit"] / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / len(trace.units()))

"""The mapper call's share of its roofline: the least time its bytes need
at the chip's HBM bandwidth (words in; the buffer and the histogram out),
over the device's busy time per call (device trace). Bandwidth bounds it:
the call does no arithmetic worth counting."""


def read(record, trace):
    if trace is None or not trace.units() or "bytes_per_unit" not in record:
        return None
    least = record["bytes_per_unit"] / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (trace.busy_s() / len(trace.units()))

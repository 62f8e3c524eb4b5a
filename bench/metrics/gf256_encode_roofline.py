"""``gf256_encode_roofline`` (%): the least time the window's stripe
parity encodes could take at the chip's HBM bandwidth, over the device
time of the Pallas kernel ``gf256_rs_encode``.  One encode per stripe
write of one vector, counted from the program's ``gf256.rs_encode``
spans.  Each encode takes a whole vector's stripe, all ``nblocks``
blocks pulled to the host, and runs on one device however the problem
is sharded: its least bytes are the whole stripe's, and its time is the
kernel's device time summed over the trace's devices."""

from bench import costs

KERNEL = "gf256_rs_encode"


def read(run):
    cfg = run.config
    if run.trace is None or "stripe_data" not in cfg:
        return None
    secs = run.trace.op_time(KERNEL)[0] * run.trace.ndevices
    encodes = sum(1 for r in run.records
                  if r["type"] == "span" and r["name"] == "gf256.rs_encode")
    if secs <= 0 or encodes == 0:
        return None
    least = encodes * costs.gf256_encode_least_bytes(
        run.nblocks, run.n // run.nblocks, cfg["stripe_data"],
        cfg["stripe_parity"], run.itemsize)
    bound_s = least / costs.chip_peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * bound_s / secs

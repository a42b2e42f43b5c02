"""quantize_roofline: the int8 codec kernels' share of their roofline,
in percent.

Every master update sends the master down and the update up through the
codec, and every evaluation sends the master down: one int8 quantize
and dequantize pass over all parameters each
(``yardstick.int8_roundtrip_bytes``).  The least time is those bytes
over the chip's peak HBM bandwidth; the share is that over the device
time of the quantize and dequantize kernels."""
from bench.yardstick import int8_roundtrip_bytes


def read(rec):
    tr = rec["trace"]
    ex = rec["config"]["execution"]
    if not tr or tr["codec_s"] <= 0:
        return None
    down = ex["downlink_codec"].startswith("int8")
    up = ex["uplink_codec"].startswith("int8")
    passes = sum(down + (up and c["kind"] == "fill") for c in rec["calls"])
    if not passes:
        return None
    need = passes * int8_roundtrip_bytes(rec["config"]["model"]["params"])
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / tr["codec_s"]

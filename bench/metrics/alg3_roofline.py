"""alg3_roofline: Algorithm 3's share of its roofline, in percent.

The bytes Algorithm 3 needs follow from its shapes, whatever implements
it (``yardstick.alg3_bytes``: every upload and the previous master read
once, the new master written once, per master update).  The least time
is those bytes over the chip's peak HBM bandwidth; the share is that
over the device time of what implements it: the operations under the
``fill_aggregate`` scope of the fused training program, or the
``fill_aggregate`` kernel's program."""
from bench.yardstick import alg3_bytes


def read(rec):
    tr = rec["trace"]
    if not tr or tr["alg3_s"] <= 0:
        return None
    params = rec["config"]["model"]["params"]
    need = sum(alg3_bytes(params, sum(len(g) for g in c["groups"]))
               for c in rec["calls"] if c["kind"] == "fill")
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / tr["alg3_s"]

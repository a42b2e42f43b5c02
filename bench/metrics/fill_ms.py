"""fill_ms: device milliseconds per generation of every program but the
fitness program: the training program (fused local SGD and Algorithm 3,
or local SGD and the Algorithm 3 kernel calls), the codec programs and
the staging copies, from the profiler trace."""


def read(rec):
    tr = rec["trace"]
    if not tr or not rec["gens"] or tr["fill_s"] <= 0:
        return None
    return 1000.0 * tr["fill_s"] / rec["gens"]

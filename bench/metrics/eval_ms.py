"""eval_ms: device milliseconds per generation of the fitness program
(``fused_eval_shared``), from the profiler trace."""


def read(rec):
    tr = rec["trace"]
    if not tr or not rec["gens"] or tr["eval_s"] <= 0:
        return None
    return 1000.0 * tr["eval_s"] / rec["gens"]

"""stage_wait_ms: milliseconds per traced generation in which the device
sat idle while the runtime copied staged arrays from the host
(``idle_by_cause["staging"]`` of ``bench/hosttrace.py``, read from the
record's ``trace``)."""


def read(rec):
    causes = (rec.get("trace") or {}).get("idle_by_cause") or {}
    if "staging" not in causes or not rec["gens"]:
        return None
    return 1000.0 * causes["staging"] / rec["gens"]

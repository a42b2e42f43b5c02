"""launch_ms: host milliseconds per generation spent calling into the
jitted programs (the program's ``launch`` spans: argument handling and
enqueue of each call, from call to return), over the traced
generations."""


def read(rec):
    spans = rec["spans"]
    found = [s for gen in spans for path, s in gen.items()
             if path.split("/")[-1] == "launch"]
    if not found:
        return None
    return 1000.0 * sum(found) / len(spans)

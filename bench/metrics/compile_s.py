"""compile_s: seconds of set-up spent tracing, lowering, compiling or
loading programs from the compilation cache: every ``compile`` span of
the set-up generations (``init`` included), read from the record's
``setup_spans``."""


def read(rec):
    found = [s for gen in rec.get("setup_spans") or []
             for path, s in gen.items() if path.split("/")[-1] == "compile"]
    if not found:
        return None
    return sum(found)

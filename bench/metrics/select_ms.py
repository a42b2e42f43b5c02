"""select_ms: host milliseconds per generation in the search strategy
(``RealTimeNas``): participant, group and offspring sampling and the
NSGA-II selection, from the program's ``sample`` and ``aggregate``
spans of the traced generations."""


def read(rec):
    spans = rec["spans"]
    if not spans:
        return None
    total = sum(s for gen in spans for path, s in gen.items()
                if path.split("/")[-1] in ("sample", "aggregate"))
    return 1000.0 * total / len(spans)

"""stage_ms: host milliseconds per generation staging the sampled
clients' shards on the device (``StackedClientBase._train_store`` and
``_test_batches``), from the program's ``download`` spans of the traced
generations."""


def read(rec):
    spans = rec["spans"]
    found = [s for gen in spans for path, s in gen.items()
             if path.split("/")[-1] == "download"]
    if not found:
        return None
    return 1000.0 * sum(found) / len(spans)

"""For the tests that hold ``BENCHMARK.json`` to "at least these, wherever
they stand": find a per-layer entry by the file it reads with, under
whatever name it has (imported by the test files beside it)."""

from chipbench import manifest


def layer_entry(man: dict, reader: str, cell_name: str) -> dict | None:
    """The per-layer entry that reads with ``layer_metrics/<reader>.json``
    in the cell ``cell_name`` (``manifest.problems`` holds the file to one
    entry a reader file and ``moves``), or None."""
    for m in manifest.metrics_of(man, "per_layer", cell_name):
        if manifest.metric_file("per_layer", m["name"]).stem == reader:
            return m
    return None

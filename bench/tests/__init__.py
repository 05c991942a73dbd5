from bench.cluster import load_json


def mix_json(name: str) -> dict:
    """A traffic mix by name: a cell's from ``bench/traffic/``, or the
    test-only ``churn10`` (no cell runs churn: see PERF.md)."""
    if name == "churn10":
        return load_json("tests/data/churn10.json")
    return load_json(f"traffic/{name}.json")

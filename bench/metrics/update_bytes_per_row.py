"""update_bytes_per_row: bytes of the model that one transition of the
compiled lane body writes, from the program's gauge
``program.update_bytes_per_row`` (set by ``build_program`` for the
plan it compiles): the rows the example reads under the row-sparse
transition, the whole model under the dense one. Exact. A program
without the gauge gives nothing to read."""

GAUGE = "program.update_bytes_per_row"


def read(ctx):
    from repro import obs

    value = obs.metrics.snapshot(GAUGE).get(GAUGE, {}).get("value")
    return float(value) if value is not None else None

"""Share of the full-attention layers' cache rows that the window's decode steps fetched: registry
counters ``decode.attn.rows_fetched`` over ``decode.attn.rows_held``, after the window less before.
The daemon counts both a step on the host from the scheduler's own lengths (``serve/sessions.py::
_decode_step``): held is slots x rows a slot, fetched is what the step's attention reads of them,
each live slot's length in whole blocks where the kernel ``decode_attention`` runs (gauge
``decode.attn.ragged_layers`` > 0) and everything held where the whole pass does, which reads 1.
None where the window counted no rows, which is also what a program without the counters reads."""


def read(run):
    def delta(name):
        after, before = (run[side]["metrics"].get("counters", {}).get("decode.attn." + name, 0)
                         for side in ("after", "before"))
        return after - before
    fetched, held = delta("rows_fetched"), delta("rows_held")
    return fetched / held if held else None

"""Share of the rows the daemon placed in HBM that were zero padding: registry counters
``store.ingest.pad_rows`` (the zero rows a matrix's blocks add past its rows) over
``store.ingest.rows`` + ``pad_rows``, counted where a matrix is blocked and stored, after the window
less before. None where no matrix was ingested in the window, which is also what a program without
the counters reads."""


def read(run):
    def delta(name):
        after, before = (run[side]["metrics"].get("counters", {}).get("store.ingest." + name, 0)
                         for side in ("after", "before"))
        return after - before
    rows, pad = delta("rows"), delta("pad_rows")
    return pad / (rows + pad) if rows + pad else None

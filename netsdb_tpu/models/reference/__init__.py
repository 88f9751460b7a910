"""Plain references of the served models: float32, no cache, no batching, no kernels.

Each file here is the twin of a ``benchmark/configs/*_reference.py`` (the copy that decides a
cell's ``correct``); ``tests/test_hybrid_lm.py`` holds the two to the same bytes."""

"""Share of the chip's idle seconds that lie under no span of any request in flight: ``gaps.py``."""
import gaps


def read(run):
    found = gaps.by_layer(run)
    if found is None or found["idle"] <= 0:
        return None
    return found["unattributed"] / found["idle"]

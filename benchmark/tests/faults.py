"""Faults for ``test_harness.py``: each breaks the timed path underneath the harness.

``launcher.py --fault benchmark/tests/faults.py:<name>`` applies one to the
program, inside the daemon, before it serves. A served deployment can have one
kind of fault: an answer altered where it is produced.
"""


def ff_answer_altered():
    """The FF output layer gives one label one and a half times its probability."""
    from netsdb_tpu.ops import nn as nn_ops

    orig = nn_ops.ff_output_layer

    def altered(y, b, axis=0):
        out = orig(y, b, axis=axis)
        return out.with_data(out.data.at[3, :].multiply(1.5))

    nn_ops.ff_output_layer = altered


def tpch_answer_altered():
    """Q6's revenue comes out a thousandth too large."""
    from netsdb_tpu.relational import table

    orig = table.ColumnTable.__init__

    def altered(self, cols=None, *args, **kwargs):
        if cols is not None and "revenue" in cols:
            cols = dict(cols, revenue=cols["revenue"] * 1.001)
        orig(self, cols, *args, **kwargs)

    table.ColumnTable.__init__ = altered

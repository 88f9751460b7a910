"""Faults for ``test_mellum2_cell.py``, put in as ``faults_trinity.py`` puts in its own.

``launcher.py --fault benchmark/tests/faults_mellum2.py:<function>`` applies one to the
program, inside the daemon, after the model is filled and before it serves.
"""


def yarn_unscaled():
    """The full layers' rotary positions without YaRN: the plain table at the same theta and
    no factor on cos and sin, as a model served past its original context without its
    scaling would have them."""
    from netsdb_tpu.models import hybrid_lm

    table = hybrid_lm.rope_table

    def unscaled(spec, kind):
        r = spec.get("rope", {}).get(kind)
        if r is None or r.get("rope_type") != "yarn":
            return table(spec, kind)
        return table(dict(spec, rope={kind: {"rope_theta": r["rope_theta"]}}), kind)

    hybrid_lm.rope_table = unscaled

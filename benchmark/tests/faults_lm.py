"""A fault for ``test_lm_cells.py``, put in as ``faults.py`` puts in the FF one.

``launcher.py --fault benchmark/tests/faults_lm.py:delta_rule_altered`` applies it
to the program, inside the daemon, after the model is filled and before it serves.
"""


def delta_rule_altered():
    """The decode step's delta-rule update writes one and a half times the correction it should:
    ``S' = alpha S + 1.5 k u^T``. The state drifts a little more with every generated token."""
    from netsdb_tpu.models import hybrid_lm
    from netsdb_tpu.ops import delta_rule

    orig = delta_rule.gated_delta_step_flat

    def altered(S, q, k, v, log_alpha, beta):
        return orig(S, q, k, v, log_alpha, 1.5 * beta)

    hybrid_lm.gated_delta_step_flat = altered

"""Faults for ``test_trinity_cell.py``, put in as ``faults_lm.py`` puts in its own.

``launcher.py --fault benchmark/tests/faults_trinity.py:<function>`` applies one to the
program, inside the daemon, after the model is filled and before it serves.
"""


def held_expert_altered():
    """The first held expert of every expert layer gives twice the output it should, in the
    kernel and in its XLA form alike: every token routed to it is wrong by that expert's part."""
    import jax.numpy as jnp

    from netsdb_tpu.ops import experts

    def altered(product):
        def grouped(xs, tile_expert, used, w_gate_up, w_down, tile):
            ys = product(xs, tile_expert, used, w_gate_up, w_down, tile)
            first = jnp.repeat(tile_expert == 0, tile)[:, None]
            return jnp.where(first, 2.0 * ys, ys)
        return grouped

    experts.grouped_ffn = altered(experts.grouped_ffn)
    experts.grouped_ffn_xla = altered(experts.grouped_ffn_xla)


def window_read_too_far():
    """A sliding layer's attention, in the decode step and in prefill, sees a quarter of a
    window more than the window: keys that a windowed layer must no longer see (and, in a
    ring, rows that newer tokens have not yet overwritten)."""
    from netsdb_tpu.models import hybrid_lm

    def wider(attend):
        def attention(*args, window=None, **kw):
            return attend(*args, window=window and window + window // 4, **kw)
        return attention

    hybrid_lm.cached_attention = wider(hybrid_lm.cached_attention)
    hybrid_lm.decode_attention = wider(hybrid_lm.decode_attention)

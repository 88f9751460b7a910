"""Seconds per request that a turn's prompt stays between its seat and its last chunk's dispatch,
under the rule of one prefill chunk an iteration, oldest turn first: the sum of spans
``session.turn.prefill`` over the window's requests. A turn without a prompt chunk adds 0. None
where the window holds no turn's spans at all, which is also what a program without them reads."""
import spans


def read(run):
    profiles = spans.window_profiles(run)
    if spans.span_seconds(profiles, lambda n: n == "session.turn.first_token") is None:
        return None
    return spans.per_request(run, spans.span_seconds(
        profiles, lambda n: n == "session.turn.prefill") or 0.0)

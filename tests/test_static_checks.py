"""Static guards, migrated onto the AST lint framework.

Every scanner that used to live here as a bespoke ~60-line AST walk is
now a typed rule in ``netsdb_tpu/analysis/rules/`` (same scope, same
intent, plus per-rule inline suppressions); each test below is the
one-line invocation the migration promised.  The full rule catalog —
including the NEW rules the bespoke scanners could never express
(lock-ordering cycles, holds-across-blocking-calls, stream-iterator
close discipline) — is documented in ``docs/ANALYSIS.md`` and gated
end-to-end by ``tests/test_lint_gate.py`` through ``cli lint``.

Run standalone: ``python tests/test_static_checks.py`` (exit 1 on
violations) — delegates to the same entry point CI uses.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # standalone-script mode
    sys.path.insert(0, REPO)


def _clean(*rule_ids: str) -> None:
    from netsdb_tpu.analysis import render, run_lint

    diags = run_lint(rules=list(rule_ids))
    assert not diags, "\n" + render(diags)


def test_serve_layer_clock_and_exception_discipline():
    _clean("wall-clock", "broad-except")


def test_zero_copy_framing_and_pickle_confinement():
    _clean("tobytes", "pickle-protocol")


def test_no_sync_device_put_in_stream_loops():
    _clean("device-put-loop")


def test_no_cache_bypassing_device_put():
    _clean("device-put-direct")


def test_obs_layer_registry_discipline():
    _clean("module-dict-counter")


def test_no_prints_outside_cli():
    _clean("print-ban")


def test_no_unsampled_qid_minting_on_hot_paths():
    _clean("qid-mint")


def test_metric_names_code_catalog_docs_agree():
    _clean("metrics-drift")


def test_lock_order_and_blocking_discipline():
    # the rules the regex era could not write: the with-lock nesting
    # graph is acyclic, and nothing blocks while holding a lock
    # without a documented suppression
    _clean("lock-order", "lock-blocking-call")


def test_stream_iterators_closed():
    _clean("iter-close")


def main() -> int:
    from netsdb_tpu.cli import main as cli_main

    return cli_main(["lint"])


if __name__ == "__main__":
    raise SystemExit(main())

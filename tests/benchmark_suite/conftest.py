"""One positional test of this directory cannot hold once the manifest
grows, and the file it lives in is not a program PR's to edit.

``test_benchmark_span_reduce.py::test_the_manifest_appends_nine_metrics_
each_with_its_reader`` pins ``per_layer[-9:]`` to PR 25's nine. The
benchmark's contract puts every new entry at the END of its list (an
entry put before those nine reads as a change to them, and the check
refused PR 29 for exactly that), so the first per-layer metric any later
PR adds breaks the slice. It is deselected here, and
``test_benchmark_latent_moe.py::test_pr25s_nine_metrics_stand_as_they_
were`` asserts everything it asserted of the same nine entries, at the
places they keep (``per_layer[7:16]``). A ``benchmark`` PR should pin
them by name in the old file and delete this one (PERF.md section 7).
"""

PINS_THE_TAIL = ("test_benchmark_span_reduce.py::"
                 "test_the_manifest_appends_nine_metrics_each_with_its_reader")


def pytest_collection_modifyitems(config, items):
    gone = [i for i in items if i.nodeid.endswith(PINS_THE_TAIL)]
    if gone:
        config.hook.pytest_deselected(items=gone)
        items[:] = [i for i in items if i not in gone]

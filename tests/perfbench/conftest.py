"""The benchmark's tests run after every other file of the suite.

Tier-1 runs under ``-n 6 --dist loadfile``, which hands files to workers in
collection order, and ``tests/perfbench`` sorts before ``tests/test_*.py``.
Run first, this one file shifts which files share a worker, and the suite
holds a pair that must not: ``tests/test_telemetry.py::
test_q3_join_drives_device_watermark_with_attribution`` fails whenever
``tests/test_pipeline_window.py`` ran before it in the same process (the
parent's files alone show it; PERF.md section 7). Last in the order, this
directory leaves every other file the schedule it had without it."""


def pytest_collection_modifyitems(items):
    here = __file__.rsplit("/", 1)[0]
    items.sort(key=lambda item: str(item.fspath).startswith(here))

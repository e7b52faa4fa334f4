"""The readers of the program's own spans and counters (engine steps,
device waits, service queue, hand-off and idle), on a tiny untraced CPU
run of the read cell, and their silence on a program that lacks them."""
from __future__ import annotations

import copy
import dataclasses
import math
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from gnnbench import cell as cellmod  # noqa: E402

READERS = {  # reader -> the program metric it reads
    "embed_wait_ms_per_query": "gnnpe_engine_step_seconds",
    "probe_wait_ms_per_query": "gnnpe_engine_step_seconds",
    "join_wait_ms_per_query": "gnnpe_engine_step_seconds",
    "device_syncs_per_query": "gnnpe_engine_device_syncs_total",
    "queue_wait_ms": "gnnpe_service_queue_wait_seconds",
    "handoff_ms_per_query": "gnnpe_service_handoff_seconds",
    "service_idle_share": "gnnpe_service_idle_seconds",
}


@pytest.fixture(scope="module")
def tiny_run():
    cell = copy.deepcopy(cellmod.resolve(cellmod.load_spec(), "nws100k.read"))
    cell.config.update(vertices=400, labels=6)
    cell.config["engine"]["vertices_per_partition"] = 200
    cell.rate = 10.0
    cell.traffic["pool"] = [{"size": 4, "count": 3, "density": "any"},
                            {"size": 8, "count": 1, "density": "dense"},
                            {"size": 8, "count": 1, "density": "sparse"}]
    run, checks = cellmod.run_cell(cell, 2**32 + 7, 1.5, False, time.perf_counter(),
                                   log=lambda *_: None)
    assert cellmod.correct(checks), checks
    return run


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_an_untraced_run(tiny_run, name):
    spec = cellmod.load_spec()
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    assert entry["moves"] == "match_p50_ms" and "workloads" not in entry
    value = cellmod.reader(name)(tiny_run)
    assert value is not None and math.isfinite(value) and value >= 0
    stage = name.split("_wait_")[0] if "_wait_ms_per_query" in name else None
    if stage is not None:  # a stage's waits lie inside the stage's time
        assert value <= cellmod.reader(f"{stage}_ms_per_query")(tiny_run)
    if name == "service_idle_share":
        assert value <= 100.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_silent_where_the_program_lacks_its_metric(tiny_run, name):
    """A program without the span or counter (an older tree) reads None."""
    gone = READERS[name]
    run = dataclasses.replace(
        tiny_run,
        counters0={k: v for k, v in tiny_run.counters0.items() if k != gone},
        counters1={k: v for k, v in tiny_run.counters1.items() if k != gone},
    )
    assert cellmod.reader(name)(run) is None

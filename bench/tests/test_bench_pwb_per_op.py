"""The ``pwb_per_op`` reader on the tiny CPU cells: the persistence writes it
counts are exactly the ones each step of the durable protocol makes."""

import pytest

from bench_tiny import TRAFFIC_CUTS, run_tiny


@pytest.mark.parametrize("cell", ["queue.zipf5050", "queue.hot1"])
def test_pwb_per_op_is_the_count_of_each_persistence_step(tmp_path, cell):
    """``pwb_per_op`` equals the pwbs worked out step by step, over the
    acknowledged ops: 3 a client to announce, 1 manifest plus each dirty
    capacity-sized leaf per touched shard, 1 response a client, and 2 epoch
    writes per touched shard."""
    from repro.obs import profile_totals

    result = run_tiny(tmp_path, cell, trace=True)
    assert result["correct"], result["checks"]
    totals = profile_totals()
    touched = totals["spans"]["retire.serialize"][0]
    dirty = totals["counters"].get("slot_leaves_written", 0)
    assert totals["counters"]["slot_leaves_inlined"] == 2 * touched  # ends, epoch
    assert dirty <= touched  # a queue shard has one capacity-sized leaf
    clients = TRAFFIC_CUTS[cell.split(".")[1]]["clients"]
    per_phase = clients * TRAFFIC_CUTS[cell.split(".")[1]]["ops_per_client"]
    acked = result["attempted"] - result["failed"]
    assert acked > 0 and acked % per_phase == 0
    phases = acked // per_phase
    pwb = 3 * clients * phases + (touched + dirty) + clients * phases + 2 * touched
    assert result["metrics"]["pwb_per_op"]["value"] == pwb / acked


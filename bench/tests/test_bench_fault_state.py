"""A serve step that hands back its KV pools unchanged makes the run
incorrect."""
import faults
import tiny


def test_unchanged_state_is_caught():
    res = tiny.run(plant=faults.unchanged_state)
    gap = res["checks"]["widest_gap"]
    assert not res["correct"] and gap["value"] > gap["limit"]

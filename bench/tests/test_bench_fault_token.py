"""A token altered where the engine emits it makes the run incorrect."""
import faults
import tiny


def test_altered_token_is_caught():
    res = tiny.run(plant=faults.altered_token)
    gap = res["checks"]["widest_gap"]
    assert not res["correct"] and gap["value"] > gap["limit"]

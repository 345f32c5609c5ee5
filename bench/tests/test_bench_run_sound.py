"""A sound run is correct, and its control is not: the plain reference
computed one precision step lower (float8 matmul inputs, int4 K and V),
put in the program's place, fails the comparison that the program's own
tokens pass."""
import tiny


def test_sound_run_is_correct_and_its_control_is_not():
    res = tiny.run()
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p99_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-2:] == ["checks", "_extra"]
    ctl = tiny.run(control=True)
    assert ctl["correct"] is False
    # the control's own reading is what was judged
    assert ctl["checks"]["widest_gap"]["value"] > \
        ctl["checks"]["widest_gap"]["limit"]

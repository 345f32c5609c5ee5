"""A traced closed-loop run reports the per-layer metrics that a CPU
trace can give, and leaves out those that need a device plane."""
import tiny


def test_traced_closed_loop_run():
    res = tiny.run(loop="closed", trace=True)
    assert res["correct"]
    got = set(res["metrics"])
    assert {"decode_step_ms.batch", "batch_occupancy_pct",
            "decode_mfu_pct"} <= got
    # no device plane on the CPU: the readers of device time find
    # nothing to read and return nothing, never 0
    assert not got & {"decode_roofline_pct", "device_idle_pct",
                      "paged_flash_attention_roofline",
                      "swiglu_qgemv_roofline"}
    assert res["device"]["window_s"] > 0
    assert 0 < res["metrics"]["batch_occupancy_pct"]["value"] <= 100

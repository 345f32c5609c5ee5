"""A whole benchmark run at a size the CPU holds: a 2-layer, width-128
int4 model behind the real gateway, engine and load client.  Only the
look for a chip is skipped."""
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = {"name": "tiny", "chips": 1}
# the program's tokens equal the reference's best at this size (widest
# gap 0.0 on every seed tried) and the control (float8 matmul inputs,
# int4 K and V) reads about 1, so the limit sits between them
LIMITS = {"sample_requests": 4,
          "widest_gap": {"limit": 0.02}}


def load(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def run(loop="open", seed=2 ** 33 + 5, seconds=2.0, trace=False,
        plant=None, control=False, config="tiny_config.json",
        limits=LIMITS):
    import cells
    import harness
    bench = cells.benchmark()
    cell = ("qwen2.5-3b-int4.chat" if loop == "open"
            else "qwen2.5-3b-int4.batch")
    return harness.run_cell(
        CELL, load(config), load(f"tiny_{loop}.json"), limits,
        seed, seconds, trace, per_layer=cells.per_layer(cell, bench),
        end_to_end=cells.end_to_end(cell, bench), t_process=time.monotonic(),
        require_tpu=False, plant=plant, control=control)

"""What the benchmark's tests share: a tiny configuration and the devices a
cell asks for."""
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# A configuration small enough for the CPU, with the generator of the real ones.
TINY = {"name": "tiny", "generator": "rmat", "a": 0.57, "b": 0.19, "c": 0.19,
        "scale": 0.01, "num_nodes": 3000, "num_edges": 40000}
SEED = 2**33 + 17


def benchmark():
    from bench import common

    return common.load_benchmark()


def devices_for(cell_name):
    import jax

    from bench import common

    return jax.devices()[: common.cell(benchmark(), cell_name)["chips"]]


def run_tiny(cell_name, *, seconds=1.0, trace=False, seed=SEED, prepare=None):
    from bench.harness import run_cell

    return run_cell(cell_name, seed, seconds, trace, devices_for(cell_name), bm=benchmark(),
                    cfg=dict(TINY), prepare=prepare)

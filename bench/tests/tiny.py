"""Tiny stand-ins for the benchmark's configurations, for CPU tests: the
same layer kinds, traffic and comparison at widths a test run can hold, in
float32, so that the program's own rounding stays far below the cells'
limits and a reading above them is the planted fault's."""

import dataclasses
import json

import harness

TINY_MODEL = {
    "dense": {"name": "tiny-dense", "family": "dense", "n_layers": 2, "d_model": 64,
              "n_heads": 4, "n_kv_heads": 2, "d_ff": 128, "vocab": 256,
              "tie_embeddings": True, "dtype": "float32", "remat": "full"},
    "ssm": {"name": "tiny-ssm", "family": "ssm", "n_layers": 2, "d_model": 64,
            "n_heads": 0, "n_kv_heads": 0, "d_ff": 0, "vocab": 256, "ssm_d_inner": 128,
            "ssm_heads": 4, "ssm_state": 16, "ssm_groups": 1, "ssm_chunk": 16,
            "conv_kernel": 4, "tie_embeddings": True, "dtype": "float32", "remat": "full"},
}


def shrink(cell: harness.Cell, seq: int = 32, layers: int | None = None) -> harness.Cell:
    """``cell`` with its model cut to test size (``layers`` deep if given)."""
    fam = cell.config["reference"]
    model = dict(TINY_MODEL[fam], **({"n_layers": layers} if layers else {}))
    config = {**cell.config, "model": model, "seq_len": seq}
    traffic = {**cell.traffic, "seq": seq}
    return dataclasses.replace(cell, config=json.loads(json.dumps(config)), traffic=traffic)


def tiny_cell(workload: str, seq: int = 32, layers: int | None = None) -> harness.Cell:
    """The benchmark's cell ``workload`` with its model cut to test size."""
    return shrink(harness.load_cell(workload), seq, layers)

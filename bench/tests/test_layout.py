"""Each configuration's layer layout lives in its own module
(``spec.load_reference``): the weights, the program's fields and the
costs.  Moving the dense decoder's there changed no reading: the same
weights bit for bit, the same FLOPs and bytes (literals from the code
before the move).  A second layout, a sparse-expert stub kept under
``bench/tests/data`` and in no cell, comes in as files alone."""
import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

from benchlib import costs, program, spec, weights

CONFIGS = spec.BENCH_DIR / "configs"
DATA = pathlib.Path(__file__).parent / "data"
BENCHLIB = spec.BENCH_DIR / "benchlib"

# sha256 (first 16 hex digits) of each leaf's bytes, two layers deep,
# from the weights code as it stood before the move (benchlib/weights.py)
LEAF_SHA = {
    ("olmo-1b", 2147495993): {
        "['blocks']['attn']['wk']": "59c1dd439a6296a6",
        "['blocks']['attn']['wo']": "77d8632e95d34f1e",
        "['blocks']['attn']['wq']": "cd0dc1636792253f",
        "['blocks']['attn']['wv']": "06edb27620bf1fe9",
        "['blocks']['ffn']['mlp']['wi_gate']": "9bf71044a82111a8",
        "['blocks']['ffn']['mlp']['wi_up']": "0d87b2e14ceef4d5",
        "['blocks']['ffn']['mlp']['wo']": "1c1fde962c391847",
        "['embed']": "6030f71f9e5ce9f9",
    },
    ("olmo-1b", 7000000001): {
        "['blocks']['attn']['wk']": "b3f67598aa79219a",
        "['blocks']['attn']['wo']": "f50da46e10c99e8e",
        "['blocks']['attn']['wq']": "351f513a18427d33",
        "['blocks']['attn']['wv']": "5f86c418415fbfd7",
        "['blocks']['ffn']['mlp']['wi_gate']": "9f098ab9e5709957",
        "['blocks']['ffn']['mlp']['wi_up']": "dc4d6529ce64c536",
        "['blocks']['ffn']['mlp']['wo']": "48067dda4853a4b0",
        "['embed']": "5398879f7e528202",
    },
    ("recllm-base", 2147495993): {
        "['blocks']['attn']['norm']['scale']": "7e846bcb03d13234",
        "['blocks']['attn']['wk']": "f0ad19d7da8e692f",
        "['blocks']['attn']['wo']": "8ac726d4d6d5e7c0",
        "['blocks']['attn']['wq']": "1698948b5d2c2659",
        "['blocks']['attn']['wv']": "c6e412cb7405c40d",
        "['blocks']['ffn']['mlp']['wi_gate']": "e3ec678f07ea5aea",
        "['blocks']['ffn']['mlp']['wi_up']": "f3ca6605ff852b3d",
        "['blocks']['ffn']['mlp']['wo']": "90585b8765efe5c8",
        "['blocks']['ffn']['norm']['scale']": "1af7222163dbd6bc",
        "['embed']": "e101c16681699f4b",
        "['final_norm']['scale']": "de6015387ff9faa9",
    },
    ("recllm-base", 7000000001): {
        "['blocks']['attn']['norm']['scale']": "0176e08b16008fe2",
        "['blocks']['attn']['wk']": "2080fd5eab69c1ba",
        "['blocks']['attn']['wo']": "91706a65c56d021b",
        "['blocks']['attn']['wq']": "9728388eb8ddbdec",
        "['blocks']['attn']['wv']": "410ab700a4bd2fd8",
        "['blocks']['ffn']['mlp']['wi_gate']": "3492d490d7abf9a1",
        "['blocks']['ffn']['mlp']['wi_up']": "bc4f5ca9a134ab00",
        "['blocks']['ffn']['mlp']['wo']": "eacb525ac6135245",
        "['blocks']['ffn']['norm']['scale']": "dc75e76abe307401",
        "['embed']": "11be89026e5caf6b",
        "['final_norm']['scale']": "c2d87cbc36e765c7",
    },
}


def _config(name: str) -> dict:
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,seed", sorted(LEAF_SHA))
def test_weights_are_the_same_bits(name, seed):
    c = {**_config(name), "num_hidden_layers": 2}
    flat = jax.tree_util.tree_flatten_with_path(weights.make_params(c, seed))[0]
    got = {jax.tree_util.keystr(k):
           hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
           for k, x in flat}
    assert got == LEAF_SHA[(name, seed)]


ROWS = [37, 100, 300, 448, 512, 1]
COSTS = [
    ("olmo-1b", "train_flops", (32, 2048), 489226839785472.0),
    ("recllm-base", "train_flops", (64, 512), 32742347636736.0),
    ("olmo-1b", "prefill_flops", (448,), 975461941248.0),
    ("olmo-1b", "prefill_flops", (32,), 68995252224.0),
    ("olmo-1b", "decode_flops", (ROWS,), 14307557376.0),
    ("olmo-1b", "decode_attn_bytes", (ROWS,), 184025088.0),
    ("olmo-1b", "decode_attn_bytes", (ROWS, 1), 92405760.0),
]


@pytest.mark.parametrize("name,fn,args,want", COSTS)
def test_costs_are_the_same_numbers(name, fn, args, want):
    assert getattr(costs, fn)(_config(name), *args) == want


def _stub_cell(tmp_path):
    """BENCHMARK.json with the stub's configuration and a training cell
    on it, the real cells and metrics beside them."""
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "moe-stub",
                             "file": "bench/tests/data/moe-stub.json"})
    bench["workloads"].append({"name": "moe.train", "config": "moe-stub",
                               "traffic": "packed-512", "chips": 1})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "recllm.train.1chip" in m.get("workloads", ()):
            m["workloads"].append("moe.train")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return spec.load_cell("moe.train", spec_path=path)


def test_a_second_layout_comes_as_files(tmp_path):
    from repro.config import get_arch, reduced
    cell = _stub_cell(tmp_path)
    c = cell.config
    assert c["reference"] == "bench/tests/data/moe_stub.py"
    want = reduced(get_arch(c["program_arch"]))
    assert all(getattr(want, k) == v
               for k, v in c["program_overrides"].items())
    cfg = program.program_config(c)
    params = weights.make_params(c, 2 ** 31 + 5)
    program.check_layout(cfg, params)
    assert params["blocks"]["ffn"]["moe"]["wi_gate"].shape == (2, 4, 64, 96)
    # 3 x 2 x (2 layers x (attention 16,384 + router 256 + 2 of 4
    # experts 36,864) + head 16,384) per token, plus causal attention
    assert costs.train_flops(c, 4, 32) == 98009088.0
    assert {m.name for m in cell.per_layer} >= {"device.step_mfu.train"}


def test_a_layout_the_program_lacks_is_refused():
    c = {**_config("olmo-1b"), "intermediate_size": 4096}
    with pytest.raises(ValueError, match="differs"):
        program.program_config(c)


# what a layout names: its leaves, widths and norms belong to its module
LAYOUT_WORDS = ["hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "num_hidden_layers",
                "wq", "wk", "wv", "wi_gate", "wi_up", "mlp", "nonparam",
                "rmsnorm", "layernorm", "embed", "lm_head"]


@pytest.mark.parametrize("path", sorted(BENCHLIB.glob("*.py"))
                         + [spec.BENCH_DIR / "run.py"],
                         ids=lambda p: p.name)
def test_harness_names_no_layout(path):
    text = path.read_text()
    assert [w for w in LAYOUT_WORDS if f'"{w}"' in text
            or f"'{w}'" in text or f".{w}" in text] == []

"""Small cells for the CPU: the cells' own files with the widths, the
traffic and the deployment cut down so that a run takes seconds."""
import copy
import json

from benchlib import spec

TINY_WIDTHS = {"num_hidden_layers": 2, "hidden_size": 64,
               "num_attention_heads": 4, "num_key_value_heads": 4,
               "head_dim": 16, "intermediate_size": 96, "vocab_size": 256,
               "vocab_pad_to": 32, "dtype": "float32"}
PROGRAM = {"num_layers": 2, "d_model": 64, "num_heads": 4,
           "num_kv_heads": 4, "head_dim": 16, "d_ff": 96,
           "vocab_size": 256, "vocab_pad_to": 32, "dtype": "float32"}


def _load(path):
    with open(path) as f:
        return json.load(f)


def config(name: str) -> dict:
    c = _load(spec.BENCH_DIR / "configs" / f"{name}.json")
    c.update(TINY_WIDTHS)
    c["program_overrides"] = dict(PROGRAM)
    return c


def serve_mix() -> dict:
    m = copy.deepcopy(_load(spec.BENCH_DIR / "traffic" / "history-cf.json"))
    m.update(rate=20.0, n_users=50, prompt_min=8, prompt_max=40,
             new_tokens_min=2, new_tokens_max=6, candidates=16, cf_dim=8,
             check_sample=6, check_group=3)
    m["deployment"].update(slots=4, max_len=64, prompt_quantum=8,
                           cf_cache_rows=16)
    return m


def train_job(name: str = "packed-512") -> dict:
    j = copy.deepcopy(_load(spec.BENCH_DIR / "traffic" / f"{name}.json"))
    j.update(seq=32, batch=4 * j["data"], doc_log_mean=2.0, doc_max=32,
             eos_id=2, check_rows=2)
    return j


REAL = {"serve": "olmo1b.serve.history-cf", "train": "recllm.train.1chip",
        "dp2tp2": "olmo1b.train.dp2tp2"}


def cell(kind: str) -> spec.Cell:
    """A Cell with every metric the real cell of that kind reports."""
    c = spec.load_cell(REAL[kind])
    c.config = config(c.config_name)
    c.traffic = (serve_mix() if kind == "serve"
                 else train_job(c.traffic_name))
    return c

"""A temporary benchmark root made of NEW files only (tiny configurations and
traffic mixes, a manifest naming them) beside links to the real code: what a
later PR adding a cell would bring. Used by the tests to load cells and to
drive every driver end to end on the CPU."""
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_CONFIG = {
    "family": "gpt", "source": "test", "activation_function": "gelu_new", "layer_norm_epsilon": 1e-5,
    "n_embd": 64, "n_head": 4, "n_inner": None, "n_layer": 2, "n_positions": 128, "vocab_size": 500,
    "reduced": [], "assumed": {"padded_vocab_size": 512},
    "serving": {"dtype": "float32", "slots": 4, "context": 128, "prefill_chunk": 16, "fuse": 1,
                "prefix_cache_mb": 0, "replicas": 1, "max_queue_depth": 64},
    "training": {"param_dtype": "float32"},
}
TINY_TRAFFIC = {
    "tiny-train": {"driver": "train", "batch": 4, "seq": 32, "amp_level": "O2", "optimizer": "AdamW",
                   "learning_rate": 1e-4, "mesh": None, "distinct_batches": 2, "warmup_steps": 2},
    "tiny-train-mesh": {"driver": "train", "batch": 4, "seq": 32, "amp_level": "O2", "optimizer": "AdamW",
                        "learning_rate": 1e-4, "distinct_batches": 2, "warmup_steps": 2,
                        "mesh": {"dp_degree": 1, "mp_degree": 2, "pp_degree": 1, "sharding_degree": 2,
                                 "sharding_stage": 2}},
    "tiny-open": {"driver": "serve_open_loop", "rate_per_s": 20.0, "stream_seed": 5,
                  "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 4, "max": 60},
                  "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.4, "min": 2, "max": 12},
                  "max_total_tokens": 128, "drain_seconds": 5,
                  "warmup_requests": [{"prompt": 37, "output": 3}, {"prompt": 9, "output": 3}]},
    "tiny-closed": {"driver": "serve_closed_loop", "clients": "slots", "stream_seed": 5,
                    "prompt_tokens": {"dist": "lognormal", "median": 20, "sigma": 0.5, "min": 4, "max": 40},
                    "output_tokens": {"dist": "lognormal", "median": 16, "sigma": 0.4, "min": 6, "max": 40},
                    "max_total_tokens": 128,
                    "first_request": {"prompt_base": 10, "prompt_step": 12, "output_share": "(client+1)/clients"},
                    "warmup_ticks": 8},
}


def make(tmp_path) -> str:
    root = str(tmp_path)
    pkg = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(pkg, "configs"))
    os.makedirs(os.path.join(pkg, "traffic"))
    for sub in ("drivers", "families", "layer_metrics", "end_to_end", "harness"):
        os.symlink(os.path.join(REPO, "benchmark", sub), os.path.join(pkg, sub))
    with open(os.path.join(pkg, "configs", "tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    for name, t in TINY_TRAFFIC.items():
        with open(os.path.join(pkg, "traffic", name + ".json"), "w") as f:
            json.dump(t, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    cells = {"tiny.train": ("tiny-train", 1), "tiny.train-mesh": ("tiny-train-mesh", 4),
             "tiny.open": ("tiny-open", 1), "tiny.closed": ("tiny-closed", 1)}
    kinds = {"tiny.train": "train", "tiny.train-mesh": "train", "tiny.open": "serve-chat",
             "tiny.closed": "serve-longgen"}

    def rehome(metric):
        # a tiny cell reports what the real cell of its kind reports
        m = dict(metric)
        if "workloads" in m:
            m["workloads"] = [c for c in cells if any(w.endswith("." + kinds[c]) for w in metric["workloads"])]
        return m

    manifest = dict(real, configs=[{"name": "tiny", "source": "test", "file": "benchmark/configs/tiny.json",
                                    "reduced": [], "why": "test"}],
                    workloads=[{"name": c, "config": "tiny", "traffic": t, "chips": n, "why": "test"}
                               for c, (t, n) in cells.items()],
                    end_to_end=[rehome(m) for m in real["end_to_end"]],
                    per_layer=[rehome(m) for m in real["per_layer"]])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root

"""Shared helpers of the benchmark's tests."""
import json
import os
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: CPU stand-ins for the chip's peaks (the harness refuses an unknown
#: device kind; tests pass these explicitly)
CPU_PEAKS = {"ops_per_s": {"bf16": 1e12, "int8": 2e12},
             "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def _load(rel):
    with open(os.path.join(REPO, rel)) as f:
        return json.load(f)


def write_smoke_root(root: str) -> str:
    """A benchmark root whose cells are the committed cells at CPU sizes:
    the same files and generators, with the widths, depth, vocabulary,
    batch and serving geometry cut so a run fits a unit test."""
    for d in ("bench/configs", "bench/traffic"):
        os.makedirs(os.path.join(root, d), exist_ok=True)

    def dump(rel, obj):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)

    lm = _load("bench/configs/starcoder2_3b-det.json")
    lm["model"].update(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                       head_dim=32, d_ff=512, vocab_size=512)
    dump("bench/configs/lm_smoke.json", lm)
    vg = _load("bench/configs/vgg16_cifar10-xnor.json")
    vg["model"].update(
        width_mult=0.125, fc=[64, 64, 10], dense_operands="float32",
        conv=[8, 8, "M", 16, 16, "M", 32, 32, 32, "M", 64, 64, 64, "M",
              64, 64, 64, "M"])
    dump("bench/configs/vgg_smoke.json", vg)
    chat = _load("bench/traffic/starcoder2_3b-det.chat.json")
    chat.update(rate_per_s=40.0, lead_in_s=0.5)
    chat["prompt_tokens"].update(median=16, min=4, max=32)
    chat["output_tokens"].update(median=6, min=2, max=16)
    chat["serving"] = {"slots": 4, "prompt_len": 32, "max_new_cap": 16,
                       "prefill_chunk": 8}
    chat["check"]["sample_requests"] = 3
    dump("bench/traffic/lm_smoke.chat.json", chat)
    img = _load("bench/traffic/vgg16_cifar10-xnor.batch256.json")
    img.update(batch=4, pool_batches=2)
    dump("bench/traffic/vgg_smoke.b4.json", img)
    bench = _load("BENCHMARK.json")
    bench["configs"] = [
        dict(bench["configs"][0], name="lm_smoke",
             file="bench/configs/lm_smoke.json"),
        dict(bench["configs"][1], name="vgg_smoke",
             file="bench/configs/vgg_smoke.json")]
    bench["workloads"] = [
        {"name": "lm_smoke.chat", "config": "lm_smoke", "traffic": "chat",
         "chips": 1, "why": "CPU-size chat"},
        {"name": "vgg_smoke.b4", "config": "vgg_smoke", "traffic": "b4",
         "chips": 1, "why": "CPU-size batch"}]
    names = {"starcoder2_3b-det.chat": ["lm_smoke.chat"],
             "starcoder2_3b-det.long_decode": [],
             "vgg16_cifar10-xnor.batch256": ["vgg_smoke.b4"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [n for w in m["workloads"] for n in names[w]]
    dump("BENCHMARK.json", bench)
    return root

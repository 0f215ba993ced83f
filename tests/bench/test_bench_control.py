"""The controls of ``correct``: the plain reference put in the program's
place and computed one precision lower must read as not correct. Run here
at sizes a unit test holds; ``bench/tools/calibrate.py`` reads the same
numbers on the chip at each cell's own size."""
import importlib.util
import json
import os

from bench import harness

from bench_helpers import REPO, write_smoke_root


def calibrate():
    path = os.path.join(REPO, "bench", "tools", "calibrate.py")
    spec = importlib.util.spec_from_file_location("bench_calibrate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def committed(rel):
    with open(os.path.join(REPO, "bench", rel)) as f:
        return json.load(f)


def test_lm_control_reads_far_above_the_program(tmp_path):
    """A 4-layer LM of 256 wide with a 4096 vocabulary, long enough outputs
    that a few hundred tokens are compared, as a run compares."""
    root = write_smoke_root(str(tmp_path))
    cfg_p = tmp_path / "bench/configs/lm_smoke.json"
    cfg = json.loads(cfg_p.read_text())
    cfg["model"].update(n_layers=4, d_model=256, n_heads=4, n_kv_heads=2,
                        head_dim=64, d_ff=1024, vocab_size=4096)
    cfg_p.write_text(json.dumps(cfg))
    tr_p = tmp_path / "bench/traffic/lm_smoke.chat.json"
    tr = json.loads(tr_p.read_text())
    tr.update(rate_per_s=20.0, lead_in_s=1.0,
              serving={"slots": 8, "prompt_len": 64, "max_new_cap": 64,
                       "prefill_chunk": 32})
    tr["prompt_tokens"].update(median=32, min=8, max=64)
    tr["output_tokens"].update(median=48, min=32, max=64)
    tr["check"]["sample_requests"] = 6
    tr_p.write_text(json.dumps(tr))
    ctrl = committed("configs/starcoder2_3b-det.json")["control"]
    harness.set_root(root)
    try:
        cell = harness.load_cell("lm_smoke.chat")
        got = calibrate().readings(cell, 1, 1.0, [ctrl])
    finally:
        harness.set_root(harness.CHECKOUT)
    assert got["tokens"] >= 150
    assert got[f"control_gap.{ctrl}"] > 3 * got["logit_gap"]


def test_vgg_control_fails_the_committed_limit(smoke_root):
    check = committed("traffic/vgg16_cifar10-xnor.batch256.json")["check"]
    ctrl = committed("configs/vgg16_cifar10-xnor.json")["control"]
    harness.set_root(smoke_root)
    try:
        cell = harness.load_cell("vgg_smoke.b4")
        got = calibrate().readings(cell, 4, 0.5, [ctrl])
    finally:
        harness.set_root(harness.CHECKOUT)
    assert got["top1_mismatch"] <= check["limits"]["top1_mismatch"]
    assert (got[f"control_mismatch.{ctrl}"]
            > check["limits"]["top1_mismatch"])

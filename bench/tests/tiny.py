"""A cell small enough for a test run on the CPU, written as the files a
later change would add: BENCHMARK.json, a configuration and a mix."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def make_root(path, traffic_overrides=None) -> str:
    root = str(path)
    os.makedirs(os.path.join(root, "bench", "configs"), exist_ok=True)
    os.makedirs(os.path.join(root, "bench", "traffic"), exist_ok=True)
    with open(os.path.join(ROOT, "bench", "configs", "table2-5000.json")) as fh:
        config = json.load(fh)
    config["name"] = "tiny"
    config["cluster"]["slaves"] = 48
    traffic = {
        "arrivals": {"offered_load": 0.4, "diurnal_amplitude": 0.6,
                     "diurnal_period_s": 86400.0},
        "groups": [{"kind": "train", "share": 0.75, "sizes": [1]},
                   {"kind": "train", "share": 0.25, "sizes": [2, 3, 4]}],
        "resident": {"count": "auto", "ramp_s": 600.0, "kind": "train"},
        "n_apps": 3000, "warmup_sim_s": 21600.0, "warm_schedule": 64,
    }
    traffic.update(traffic_overrides or {})
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"] = [{"name": "tiny", "source": "https://arxiv.org/abs/1704.06738",
                         "file": "bench/configs/tiny.json", "reduced": ["slaves"],
                         "why": "test size"}]
    bench["workloads"] = [{"name": "tiny.mix", "config": "tiny",
                           "traffic": "mix", "chips": 1, "why": "test size"}]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    for name, obj in (("BENCHMARK.json", bench),
                      ("bench/configs/tiny.json", config),
                      ("bench/traffic/mix.json", traffic)):
        with open(os.path.join(root, name), "w") as fh:
            json.dump(obj, fh)
    return root

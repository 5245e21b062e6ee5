"""A tiny copy of the benchmark's data files, for the CPU tests: the
manifest and every metric and traffic file as committed, the two
configurations cut to a few thousand documents. The code under test is
the package's own; only the DATA root moves."""

import json
import os
import shutil

from benchmark.manifest import ROOT, Manifest

TINY_DOCS = {"text": 6000, "vectors": 3000}
TINY_VOCAB = 5000


def tiny_root(tmp: str) -> str:
    os.makedirs(os.path.join(tmp, "benchmark", "configs"))
    for d in ("metrics", "traffic"):
        shutil.copytree(os.path.join(ROOT, "benchmark", d),
                        os.path.join(tmp, "benchmark", d))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    for c in doc["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        cfg["corpus"]["docs"] = TINY_DOCS[cfg["kind"]]
        if cfg["kind"] == "text":
            cfg["corpus"]["vocab"] = TINY_VOCAB
        with open(os.path.join(tmp, c["file"]), "w") as f:
            json.dump(cfg, f)
    tdir = os.path.join(tmp, "benchmark", "traffic")
    for name in os.listdir(tdir):
        with open(os.path.join(tdir, name)) as f:
            t = json.load(f)
        # a test worker shares its cores with five others: few client
        # threads, a short prefill
        t["connections"] = 4
        warm = t.setdefault("warmup", {})
        warm["buckets"] = [1, 4]
        if warm.get("prefill"):
            warm["prefill"] = 24
        if warm.get("lead_in_s"):
            warm["lead_in_s"] = 0.5
        with open(os.path.join(tdir, name), "w") as f:
            json.dump(t, f)
    return tmp


def tiny_manifest(tmp: str) -> Manifest:
    return Manifest(tiny_root(tmp))


def steer_engines(mp, cache_dir: str) -> None:
    """The test knobs that stand in for the TPU backend gate and the real
    thresholds (tiny segments reach none of them), as in
    tests/test_chip_smoke.py: steered here, not by an option of the
    benchmark."""
    mp.setenv("ES_TPU_FORCE_TURBO", "1")
    mp.setenv("ES_TPU_FORCE_KNN", "1")
    mp.setenv("ES_TPU_TURBO_COLD_DF", "32")
    mp.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)

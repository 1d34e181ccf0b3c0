"""End-to-end behaviour tests for the paper's system: launcher runs,
fault-tolerant restart drill, the full microbiome-style analysis
pipeline, and serving."""

import argparse
import os

import jax.numpy as jnp
import numpy as np

from repro.core import DistanceMatrix, mantel, pcoa
from repro.data.distance import DistanceTileStream
from repro.launch import serve as serve_launch
from repro.launch import train as train_launch


def _args(**kw):
    ap = train_launch.build_argparser()
    base = ["--arch", kw.pop("arch")]
    for k, v in kw.items():
        base += ([f"--{k.replace('_', '-')}"] if v == "" else
                 [f"--{k.replace('_', '-')}", str(v)])
    base.append("--smoke")
    return ap.parse_args(base)


def test_train_launcher_loss_decreases():
    """~100k-param model, structured data: loss must fall measurably."""
    res = train_launch.run(_args(arch="llama3.2-3b", steps=30, batch=8,
                                 seq=64, lr="3e-3"))
    first = np.mean(res["losses"][:5])
    last = np.mean(res["losses"][-5:])
    assert last < first - 0.2, (first, last)


def test_train_restart_is_seamless(tmp_path):
    """Kill-and-resume drill: 4+4 resumed steps ≡ 8 straight steps."""
    ck1 = str(tmp_path / "a")
    ck2 = str(tmp_path / "b")
    # decay_steps pinned to the full horizon so the LR schedule is
    # restart-invariant (the interrupted run must see the same schedule)
    r_full = train_launch.run(_args(arch="qwen3-8b", steps=8, batch=4,
                                    seq=32, ckpt_dir=ck1, ckpt_every=4,
                                    decay_steps=8))
    train_launch.run(_args(arch="qwen3-8b", steps=4, batch=4, seq=32,
                           ckpt_dir=ck2, ckpt_every=4, decay_steps=8))
    r_resumed = train_launch.run(_args(arch="qwen3-8b", steps=8, batch=4,
                                       seq=32, ckpt_dir=ck2, ckpt_every=4,
                                       decay_steps=8, resume=""))
    # identical data (step-keyed) + identical state ⇒ identical tail losses
    np.testing.assert_allclose(r_full["losses"][4:], r_resumed["losses"],
                               rtol=1e-4, atol=1e-4)


def test_serve_launcher_continuous_batching(monkeypatch):
    """The analysis-service launcher: every request of a mixed battery
    ends ``done`` over coalesced tiles, and a request that does not end
    ``done`` makes ``main`` exit non-zero."""
    res = serve_launch.run(argparse.Namespace(
        studies=3, requests=6, n=24, batch=8, permutations=49, seed=0,
        show=0, json=False))
    assert res["statuses"] == ["done"] * 6
    assert res["scheduler"]["tiles_run"] > 0
    # the environment names a cache directory, so main leaves JAX's
    # cache configuration alone (JAX read the variable at import)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
    assert serve_launch.main(["--smoke", "--requests", "4"]) == 0
    # one study: request 5's partial Mantel controls for the very study
    # it correlates with, which is refused as collinear
    assert serve_launch.main(["--smoke", "--studies", "1",
                              "--requests", "5"]) == 1


def test_microbiome_pipeline_end_to_end():
    """The paper's full downstream pipeline: distance matrix (streamed)
    → validation → PCoA → Mantel against a perturbed matrix."""
    ds = DistanceTileStream(n=96, tile=32, seed=0, dim=4)
    dm = DistanceMatrix(ds.dense())            # validates (fused pass)
    res = pcoa(dm, dimensions=4, method="fsvd")
    assert res.coordinates.shape == (96, 4)
    ev = np.asarray(res.eigenvalues)
    assert (ev[:4] > 0).all()

    ds2 = DistanceTileStream(n=96, tile=32, seed=0, dim=4)
    noise = 0.01 * np.abs(np.random.default_rng(0).normal(size=(96, 96)))
    noise = np.triu(noise, 1)
    d2 = np.asarray(ds2.dense()) + noise + noise.T
    dm2 = DistanceMatrix(jnp.asarray(d2))
    stat, p, _ = mantel(dm, dm2, permutations=49)
    assert stat > 0.99
    assert p <= 0.04


def test_quickstart_example_runs():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "quickstart", os.path.join(os.path.dirname(__file__), "..",
                                   "examples", "quickstart.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.main(fast=True)
    assert out["pcoa_dims"] >= 2
    assert 0 < out["mantel_p"] <= 1

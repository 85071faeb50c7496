"""Guards against JAX API-drift reintroductions.

The seed repo shipped with its whole distributed suite dead because one
module referenced ``jax.sharding.AxisType`` (absent on the installed JAX).
These tests pin the two invariants that prevent a recurrence:

  1. ``repro.compat`` + ``repro.launch.mesh`` import and build meshes on the
     *installed* JAX — whatever its version;
  2. no module outside ``repro/compat.py`` touches a version-gated JAX
     symbol directly.

The second family used to be regex greps living here; they are now thin
wrappers over the AST lint engine (``repro.analysis.lint``), which resolves
import aliases (``from jax.experimental import shard_map as sm`` no longer
slips through) and does not false-positive on docstring prose. The
allowlists live on the rules themselves in ``repro/analysis/rules.py``.
"""
import os

import jax
import numpy as np
import pytest

from repro.analysis import run_lint

SRC = os.path.join(os.path.dirname(__file__), "..", "src", "repro")


def _lint(rule_id):
    return run_lint([SRC], select=[rule_id])


def test_no_version_gated_jax_symbols_outside_compat():
    result = _lint("jax-version-gated")
    offenders = [str(f) for f in result.findings] + [str(f) for f in result.waived]
    assert not offenders, (
        "version-gated JAX symbols outside repro/compat.py:\n" + "\n".join(offenders))


def test_no_custom_vjp_spines_outside_core_site():
    """Exactly ONE sketched-site ``custom_vjp`` spine exists: the local and
    TP execution plans all route through ``core/site.py``. Any new
    ``jax.custom_vjp`` in ``src/`` is a second spine in the making — the
    exact duplication (sketched_linear + the three sharded_sketch builds)
    this repo just collapsed — unless explicitly allowlisted on the rule.

    Allowlist (see CustomVjpRule): core/site.py (THE spine);
    launch/pipeline.py (the pipeline-parallel stage-boundary vjp — not a
    sketched site). The serve/ and kernels/ trees currently define none; a
    Pallas kernel or decode path that genuinely needs its own vjp must be
    added there explicitly, with a comment.

    Inline ``# lint: waive=`` comments are also treated as offenders here:
    a second spine cannot be self-waived at the call site."""
    result = _lint("custom-vjp-outside-site")
    offenders = [str(f) for f in result.findings] + [str(f) for f in result.waived]
    assert not offenders, (
        "new custom_vjp spine outside core/site.py — route the site through "
        "the one spine (SiteSpec/ExecutionPlan) or extend the allowlist "
        "explicitly:\n" + "\n".join(offenders))


def test_no_ctx_construction_outside_api_and_nn():
    """The Runtime front door owns Ctx construction: outside ``repro/nn``
    (where Ctx lives and re-derives per-layer children) and ``repro/api``
    (whose ExecutionConfig.make_ctx is the sanctioned factory), no module may
    build a ``Ctx(...)`` directly — that is how train() kwargs smeared across
    the codebase in the first place. Use ``Runtime.ctx`` /
    ``ExecutionConfig.make_ctx`` instead."""
    result = _lint("ctx-outside-api-nn")
    offenders = [str(f) for f in result.findings] + [str(f) for f in result.waived]
    assert not offenders, (
        "direct Ctx(...) construction outside repro/api + repro/nn "
        "(route through ExecutionConfig.make_ctx / Runtime.ctx):\n"
        + "\n".join(offenders))


def test_compat_and_mesh_import_and_build_2x2():
    """The exact seed failure mode: mesh construction on the installed JAX."""
    from repro import compat
    from repro.launch import mesh as meshlib

    if jax.device_count() < 4:
        pytest.skip("needs >=4 (fake) devices")
    m = meshlib.make_mesh((2, 2), ("data", "model"))
    assert m.axis_names == ("data", "model")
    assert dict(m.shape) == {"data": 2, "model": 2}
    assert meshlib.dp_axes(m) == ("data",)
    assert meshlib.mp_axes(m) == ("model",)
    # compat.make_mesh is the same construction path
    m2 = compat.make_mesh((2, 2), ("data", "model"))
    assert m2.axis_names == m.axis_names

    # meshes are usable: a trivial sharded reduction runs on the installed JAX
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = np.arange(8, dtype=np.float32).reshape(4, 2)
    y = jax.jit(lambda a: a.sum(),
                in_shardings=(NamedSharding(m, P("data", "model")),))(x)
    assert float(y) == x.sum()


def test_compat_shard_map_runs():
    from repro import compat

    if jax.device_count() < 4:
        pytest.skip("needs >=4 (fake) devices")
    from jax.sharding import PartitionSpec as P

    m = compat.make_mesh((4,), ("data",))
    x = np.arange(16, dtype=np.float32).reshape(4, 4)

    def body(x_l):
        return jax.lax.psum(x_l.sum(), "data")

    out = compat.shard_map(body, mesh=m, in_specs=(P("data", None),),
                           out_specs=P())(x)
    assert float(out) == x.sum()


def test_compat_tree_and_key_helpers():
    from repro import compat

    t = {"a": np.ones(2), "b": [np.zeros(1)]}
    leaves = compat.tree_leaves(t)
    assert len(leaves) == 2
    flat, treedef = compat.tree_flatten(t)
    back = compat.tree_unflatten(treedef, flat)
    assert compat.tree_structure(back) == treedef
    doubled = compat.tree_map(lambda x: x * 2, t)
    np.testing.assert_array_equal(doubled["a"], np.full(2, 2.0))

    k = compat.prng_key(0)
    assert jax.random.bits(jax.random.fold_in(k, 1), (2,)).shape == (2,)
    assert compat.key_dtype() == k.dtype


def test_compilation_cache_placement(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set in code;
    without it the cache goes to one fixed, gitignored directory inside
    the checkout."""
    from repro import compat

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compat.enable_compilation_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
        assert compat.enable_compilation_cache() == compat.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == compat.CACHE_DIR
        assert os.path.dirname(compat.CACHE_DIR) == root
        with open(os.path.join(root, ".gitignore")) as f:
            ignored = f.read().split()
        assert os.path.basename(compat.CACHE_DIR) + "/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# Trains a few donating steps with the persistent cache on (every executable
# cached, however quick its compile) and prints the losses, a digest of the
# final state's bytes, and how many executables were read back from the cache.
_CACHED_TRAIN = r"""
import hashlib, json
import jax, numpy as np
from repro import compat
compat.enable_compilation_cache()
hits = []
jax.monitoring.register_event_listener(
    lambda name, **kw: hits.append(name)
    if name == "/jax/compilation_cache/cache_hits" else None)
from repro.api import Runtime, SketchConfig, SketchPolicy
from repro.configs import gemma3_1b
from repro.data.synthetic import LMStream
from repro.optim import adamw
from repro.train.trainer import TrainerConfig
cfg = gemma3_1b.SMOKE
rt = Runtime(policy=SketchPolicy(base=SketchConfig(method="l1", budget=0.5)))
state, hist = rt.train(cfg, adamw(1e-3),
                       LMStream(vocab=cfg.vocab, seed=0).batches(2, 16),
                       TrainerConfig(steps=6, log_every=1, seed=0))
digest = hashlib.sha256()
for leaf in jax.tree.leaves(jax.device_get(state)):
    digest.update(np.ascontiguousarray(leaf).tobytes())
print(json.dumps({"losses": [h["loss"] for h in hist],
                  "state": digest.hexdigest(), "hits": len(hits)}))
"""


def test_reloaded_train_step_keeps_donation(tmp_path):
    """A donating train step read back from the persistent compilation cache
    trains bit-identically to the freshly compiled one. (Executables reloaded
    from the cache once lost their input-output aliasing, so the donated
    state chain read freed buffers with no error.)"""
    import json
    import subprocess
    import sys

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(root, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")

    def run():
        out = subprocess.run([sys.executable, "-c", _CACHED_TRAIN], env=env,
                             cwd=str(tmp_path), capture_output=True, text=True,
                             timeout=600)
        assert out.returncode == 0, out.stderr[-4000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = run()
    assert os.listdir(tmp_path / "cache")
    warm = run()
    assert cold["hits"] == 0 and warm["hits"] > 0
    assert warm["losses"] == cold["losses"]
    assert warm["state"] == cold["state"]

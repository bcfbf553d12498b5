"""CPU checks of the path ``chip_smoke.py`` drives on the chip: the device
check, the published-width launcher option, the compile-cache placement,
one fleet replica per device, the decoder pick rules, and sampling inside
the vocabulary."""
import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import decode_backends as db
from repro.launch import compile_cache, serve
from repro.models import api

ROOT = Path(__file__).resolve().parents[1]


def test_chip_smoke_exits_nonzero_without_tpu():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert isinstance(e.value.code, str)       # non-zero exit with a message
    assert "not a TPU" in e.value.code and "nothing was run" in e.value.code


def test_published_layers_keeps_every_width():
    args = serve.parse_args(["--arch", "qwen3-1.7b", "--published-layers",
                             "4"])
    cfg = serve.model_config(args)
    # the init serve.build runs, traced for shapes only (nothing allocated)
    shapes = jax.eval_shape(
        lambda: api.build(cfg).init(cfg, jax.random.PRNGKey(0)))
    L, D, V = 4, 2048, cfg.padded_vocab()
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
            cfg.d_ff, cfg.vocab) == (4, 2048, 16, 8, 128, 6144, 151936)
    want = {"embed": (V, D), "lm_head": (D, V), "final_norm": (D,),
            "layers/wq": (L, D, 16 * 128), "layers/wk": (L, D, 8 * 128),
            "layers/wv": (L, D, 8 * 128), "layers/wo": (L, 16 * 128, D),
            "layers/w_gate": (L, D, 6144), "layers/w_up": (L, D, 6144),
            "layers/w_down": (L, 6144, D), "layers/attn_norm": (L, D),
            "layers/mlp_norm": (L, D), "layers/q_norm": (L, 128),
            "layers/k_norm": (L, 128)}
    assert {k: v.shape for k, v in shapes.items()} == want
    # the default stays the reduced smoke config
    small = serve.model_config(serve.parse_args(["--arch", "qwen3-1.7b"]))
    assert (small.d_model, small.vocab) == (128, 512)
    with pytest.raises(SystemExit):
        serve.parse_args(["--arch", "qwen3-1.7b", "--published-layers",
                          "29"])


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_placement(monkeypatch, restore_cache_dir, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # left to JAX
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    got = compile_cache.setup_compile_cache()
    assert got == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got


def test_serve_replicas_land_on_distinct_devices(monkeypatch, capsys,
                                                 tmp_path):
    assert len(jax.local_devices()) >= 4       # tests/conftest.py forces 8
    # an environment-set cache dir keeps the helper from touching jax config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = serve.main(["--arch", "qwen3-1.7b", "--batch-slots", "2",
                     "--traffic", "4", "--prompt-len", "8", "--gen", "2",
                     "--replicas", "4"])
    assert rc == 0
    out = capsys.readouterr().out
    line = next(l for l in out.splitlines() if "replica devices:" in l)
    placed = re.findall(r"r(\d) (\S+?)(?:,|$)", line.split(":", 1)[1])
    assert [int(i) for i, _ in placed] == [0, 1, 2, 3]
    assert len({d for _, d in placed}) == 4
    assert "4/4 requests" in out


@pytest.mark.parametrize("platform", ["cpu", "gpu", "tpu"])
@pytest.mark.parametrize("lowers", [False, True])
def test_auto_pick_never_takes_a_backend_its_rule_refuses(
        monkeypatch, platform, lowers):
    from repro.kernels.fused_decode_matmul import default_fused_impl
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    monkeypatch.setattr(db, "PALLAS_DECODE_LOWERS", lowers)
    picked = db.auto_pick()
    assert picked.auto_eligible() and picked.available()
    for b in db._REGISTRY.values():
        if b.priority > picked.priority:
            assert not b.auto_eligible(), b.name
    want = {"cpu": "numpy", "gpu": "jax",
            "tpu": "pallas" if lowers else "jax"}[platform]
    assert picked.name == want
    assert default_fused_impl() == (
        "pallas" if lowers and platform == "tpu" else "jax")
    assert db.get_backend("pallas-interpret").auto_eligible() is False
    # compiled Pallas can be named only on a TPU, where the compiler answers
    assert db._REGISTRY["pallas"].available() == (platform == "tpu")
    assert f"{want} picked" in db.auto_pick_reason()


def test_sampling_never_emits_padded_vocab_ids():
    from repro.serving.batching.engine import _sample_slots
    from repro.serving.engine import sample
    V, vocab = 8, 6
    logits = jnp.zeros((3, 1, V)).at[:, :, vocab:].set(100.0)
    key = jax.random.PRNGKey(0)
    for t in (0.0, 1.0):
        assert (np.asarray(sample(logits, key, t, vocab)) < vocab).all()
    keys = jnp.asarray(np.stack([np.asarray(jax.random.PRNGKey(i))
                                 for i in range(3)]))
    for temps in (jnp.zeros(3), jnp.ones(3)):
        toks, _ = _sample_slots(logits, keys, temps, vocab)
        assert (np.asarray(toks) < vocab).all()

"""What keeps the device from being quietly stood in for (ISSUE 22): the
compile-cache placement, the engine's resolved-backend report and replica
placement, the pinned-host rule, and ``chip_smoke.py`` refusing the CPU."""

import logging
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
from llmd_kv_cache_tpu.models.llama import LlamaConfig, init_params
from llmd_kv_cache_tpu.offload import tpu_copier
from llmd_kv_cache_tpu.utils import compile_cache


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(jax.random.PRNGKey(0), LlamaConfig.tiny())


class TestCompileCachePlacement:
    @pytest.fixture(autouse=True)
    def _restore_config(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_var_wins_and_no_path_is_set_in_code(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_one_fixed_ignored_path(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = compile_cache.enable_compile_cache()
        assert first == compile_cache.enable_compile_cache()
        assert first == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


class TestResolvedBackends:
    def test_cpu_auto_is_xla_and_says_so(self, tiny_params):
        eng = MiniEngine(EngineConfig(), params=tiny_params)
        b = eng.attention_backends
        assert b["platform"] == "cpu"
        assert b["decode"] == {"backend": "xla", "interpret": False}
        assert b["prefill"] == {"backend": "xla", "interpret": False}
        assert b["ragged"] is None
        b["decode"]["backend"] = "edited"  # a copy: the record is read-only
        assert eng.attention_backends["decode"]["backend"] == "xla"

    def test_forced_pallas_on_cpu_is_interpreted(self, tiny_params):
        eng = MiniEngine(
            EngineConfig(use_pallas_decode=True, use_pallas_prefill=True,
                         ragged_attention=True), params=tiny_params)
        b = eng.attention_backends
        for ph in ("decode", "prefill", "ragged"):
            assert b[ph] == {"backend": "pallas", "interpret": True}

    def test_on_a_tpu_nothing_is_interpreted_and_fallbacks_warn(
            self, tiny_params, monkeypatch, caplog):
        from llmd_kv_cache_tpu.models import engine as engine_mod

        chip = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        monkeypatch.setattr(engine_mod.jax, "devices", lambda *a: [chip])
        aligned = LlamaConfig(vocab_size=64, hidden_size=32, num_layers=1,
                              num_heads=2, num_kv_heads=1, head_dim=128,
                              intermediate_size=64, page_size=16)
        eng = MiniEngine(EngineConfig(model=aligned, ragged_attention=True),
                         params=init_params(jax.random.PRNGKey(0), aligned))
        b = eng.attention_backends
        assert b["device_kind"] == "TPU v5 lite"
        for ph in ("decode", "prefill", "ragged"):
            assert b[ph] == {"backend": "pallas", "interpret": False}
        # head_dim 16 cannot go through Mosaic: the auto setting drops to
        # XLA, and now says so without being asked explicitly.
        with caplog.at_level(logging.WARNING):
            eng = MiniEngine(EngineConfig(), params=tiny_params)
        assert eng.attention_backends["decode"]["backend"] == "xla"
        assert "not 128-aligned" in caplog.text

    def test_telemetry_debug_vars_carry_the_report(self, tiny_params):
        from llmd_kv_cache_tpu.telemetry import EngineTelemetryConfig

        eng = MiniEngine(EngineConfig(telemetry=EngineTelemetryConfig()),
                         params=tiny_params)
        assert (eng.telemetry.debug_vars()["attention_backends"]
                == eng.attention_backends)


class TestReplicaPlacement:
    def test_device_pins_weights_pools_and_steps(self, tiny_params):
        """One replica per device of the virtual mesh: everything the
        engine holds, and the outputs of its steps, stay on its device."""
        dev = jax.devices()[3]
        prompt = list(range(1, 40))
        home = MiniEngine(EngineConfig(), params=tiny_params)
        away = MiniEngine(EngineConfig(), params=tiny_params, device=dev)
        assert away.generate("r", prompt, 6) == home.generate("r", prompt, 6)
        leaves = jax.tree_util.tree_leaves(away.params)
        assert {d for x in leaves for d in x.devices()} == {dev}
        # The pools are outputs of the last jitted step.
        assert away.k_cache.devices() == {dev}
        assert away.v_cache.devices() == {dev}
        assert home.k_cache.devices() == {jax.devices()[0]}

    def test_copier_keeps_the_pools_commitment(self):
        """A restore must not change the pools' jit signature: a committed
        slab scattered into uncommitted pools commits them, and every
        jitted step then recompiles."""
        import jax.numpy as jnp

        def pool(device=None):  # scatter donates: fresh pools each time
            return jax.device_put(jnp.zeros((1, 4, 1, 4, 8), jnp.bfloat16),
                                  device)

        copier = tpu_copier.TPUBlockCopier(pool(), pool())
        slab = copier.gather_to_host([1, 2])
        copier.scatter_from_host(slab, [1, 2])
        assert not copier.k_cache.committed
        dev = jax.devices()[3]
        copier = tpu_copier.TPUBlockCopier(pool(dev), pool(dev))
        copier.scatter_from_host(slab, [1, 2])
        assert copier.k_cache.committed
        assert copier.k_cache.devices() == {dev}

    def test_device_and_mesh_are_exclusive(self, tiny_params):
        from llmd_kv_cache_tpu.parallel.mesh import make_mesh

        with pytest.raises(ValueError, match="not both"):
            MiniEngine(EngineConfig(), params=tiny_params,
                       device=jax.devices()[1],
                       mesh=make_mesh({"tp": 2}, jax.devices()[:2]))


class TestPinnedHostRule:
    @staticmethod
    def _pool(platform):
        dev = SimpleNamespace(platform=platform, id=0)
        return SimpleNamespace(shape=(1, 4, 1, 4, 8), dtype="bfloat16",
                               committed=False, devices=lambda: [dev])

    @pytest.fixture
    def no_memory_kinds(self, monkeypatch):
        def refuse(*_a, **_kw):
            raise ValueError("memory kind pinned_host not found")

        monkeypatch.setattr(tpu_copier.jax.sharding, "SingleDeviceSharding",
                            refuse)

    def test_refused_placement_is_an_error_on_a_tpu(self, no_memory_kinds):
        pool = self._pool("tpu")
        with pytest.raises(RuntimeError, match="pinned_host memory"):
            tpu_copier.TPUBlockCopier(pool, pool)

    def test_elsewhere_it_degrades_and_says_so(self, no_memory_kinds):
        pool = self._pool("cpu")
        assert not tpu_copier.TPUBlockCopier(pool, pool).pinned_host_active


class TestChipSmoke:
    def test_no_tpu_no_result(self, capsys):
        import chip_smoke

        with pytest.raises(SystemExit) as exc:
            chip_smoke.find_device(need_tpu=True)
        assert exc.value.code == 1
        out, err = capsys.readouterr()
        assert "no TPU" in err and "'cpu'" in err
        assert '"ok"' not in out

    @pytest.mark.slow  # ~35 s of interpret-mode compiles; `make test` runs it
    def test_rehearsal_walks_every_phase_and_gives_no_verdict(self, capsys):
        import chip_smoke

        S = chip_smoke.run_one_chip(SimpleNamespace(layers=0), rehearse=True)
        assert set(S.phases) >= {"kernels vs reference", "routed traffic",
                                 "replicas agree", "offload round trip"}
        assert S.routing["mixed_steps"] > 0
        assert S.offload["restored_on"] in S.backends
        assert '"ok"' not in capsys.readouterr().out

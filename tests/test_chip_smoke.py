"""chip_smoke.py's own logic, at a toy width on the CPU.

The script refuses to run without a TPU, so its phases are functions
with the sizes as arguments and the tests call those: the HTTP driving,
the teacher-forced logits check, the census, the re-lowering that lists
each program's kernels, and the trainer loop all run here, so that a
change to the runner's signatures or the server's metrics breaks a test
and not the next chip call.
"""
import json
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import chip_smoke as C  # noqa: E402

from paddle_tpu.models.bert import BertConfig  # noqa: E402
from paddle_tpu.models.llama import llama_tiny  # noqa: E402
from paddle_tpu.utils import compile_cache  # noqa: E402

LENGTHS = (200, 20, 24, 40, 48)


def tiny_llama():
    cfg = llama_tiny(num_attention_heads=8, num_key_value_heads=4,
                     max_position_embeddings=512)
    cfg.dtype = "bfloat16"
    return cfg


@pytest.fixture(scope="module")
def served():
    cfg = tiny_llama()
    model = C.build_llama(cfg, C.SEED)
    prompts = C.make_prompts(cfg.vocab_size, LENGTHS, C.SEED)
    out = C.drive_server(model, prompts, (6, 8), max_model_len=256)
    return model, prompts, out


def test_serve_phase_answers_three_groups(served):
    _, _, out = served
    assert [len(t) for t in out["served"]] == [6, 8, 8, 8, 8, 6]
    assert out["hit_pages"] == 12           # 200 // 16 pages of the repeat
    assert out["census"]["leak"] == 0 and out["census"]["live"] == 0
    assert out["decode_traces"] == 1
    assert {"decode_step", "prefill[208]",
            "prefill_cached[16]"} <= set(out["compile_s"])


def test_served_tokens_pass_the_reference(served):
    model, prompts, out = served
    check = C.check_served(model, prompts, out["served"])
    assert check["worst_gap"] <= C.LOGIT_TOL
    assert check["long"]["positions"] == 12
    assert check["short"]["positions"] == 32
    assert model.config.use_flash_attention     # the switch is put back


def test_a_wrong_token_fails_the_reference(served):
    """The check has teeth: tokens served for ANOTHER prompt are far
    outside the tolerance."""
    model, prompts, out = served
    swapped = list(out["served"])
    swapped[1], swapped[2] = swapped[2], swapped[1]
    with pytest.raises(RuntimeError, match="leave the reference"):
        C.check_served(model, prompts, swapped)


def test_runner_kernels_relowers_every_program(served):
    _, _, out = served
    kernels = C.runner_kernels(out["runner"])
    assert set(kernels) == {"decode_step", "prefill[32]", "prefill[48]",
                            "prefill[208]", "prefill_cached[16]"}
    assert all(v == {} for v in kernels.values())   # the CPU has none


def test_serve_phase_on_a_virtual_mesh():
    """What --four-chips runs, at toy width on the virtual CPU devices:
    tp=2 beside tp=1 from one model, shards counted per device."""
    devices = jax.devices()[:2]
    out = C.serve_phase(tiny_llama(), devices, seed=C.SEED,
                        meshes=("tp=2", None), lengths=LENGTHS,
                        new_tokens=(6, 8), max_model_len=256)
    two, one = out["tp2"]["weight_bytes"], out["tp1"]["weight_bytes"]
    assert len(two) == 2 and two[0] == two[1] < one[0]
    assert out["tp2"]["pool_bytes"][0] * 2 == out["tp1"]["pool_bytes"][0]
    for run in (out["tp2"], out["tp1"]):
        assert run["logits"]["worst_gap"] <= C.LOGIT_TOL
        assert run["census"]["leak"] == 0 and run["decode_traces"] == 1
    json.dumps(out)


def test_hybrid_phase_layouts_agree():
    cfg = tiny_llama()
    out = C.hybrid_phase(cfg, jax.devices()[:4], seed=C.SEED, batch=4,
                         seq=128)
    a, b = out["pp1_dp1_tp4"], out["pp1_dp2_tp2"]
    assert out["first_loss_spread"] <= C.LOSS_TOL
    assert a["params"] == b["params"]
    # tp=4 holds a quarter of every sharded leaf, dp=2 x tp=2 a half, and
    # the Adam state sits where the params do (f32 m and v: 4x the bytes)
    assert len(set(a["param_bytes"])) == 1 and len(set(b["param_bytes"])) == 1
    assert a["param_bytes"][0] < b["param_bytes"][0]
    assert a["adam_bytes"][0] == 4 * a["param_bytes"][0]
    json.dumps(out)


def test_shard_bytes_refuses_a_leaf_left_on_one_device():
    import jax.numpy as jnp
    devices = jax.devices()[:2]
    with pytest.raises(RuntimeError, match="not on all of"):
        C.shard_bytes({"w": jnp.zeros((4, 4))}, devices)


def test_train_phase_loss_falls():
    cfg = BertConfig(vocab_size=512, hidden_size=128, num_hidden_layers=2,
                     num_attention_heads=4, intermediate_size=256)
    out = C.train_phase(cfg, jax.devices()[:1], seed=C.SEED, batch=2,
                        seq=64, autocast=False)
    assert out["losses"][-1] < out["losses"][0]
    assert np.all(np.isfinite(out["losses"]))
    json.dumps(out)                         # every line it prints is JSON


@pytest.mark.parametrize("groups", [1, 4])
def test_ssm_update_phase_checks_every_parking(monkeypatch, groups):
    """The state-update kernel's chip check at a toy size, under the
    Pallas interpreter: every way of parking in both dtypes at both
    block rules (with 4 groups of 64 lanes: a block that is one group,
    and one that holds two), and a kernel that touches a parked slot is
    refused."""
    from paddle_tpu.ops.pallas import ssm_update as U
    monkeypatch.setattr(U, "_INTERPRET", True)
    out = C.ssm_update_phase(seed=C.SEED, slots=6, n=16, hp=256,
                             groups=groups, lane_blocks=(64, 128, 96),
                             reps=1)
    assert out["groups"] == groups
    assert len(out["checks"]) == 2 * 2 * len(C.PARKED)  # 96 divides nothing
    assert all(c["parked_untouched"] and c["other_layers_untouched"]
               for c in out["checks"].values())
    assert out["checks"]["bfloat16.lanes128.all"]["live"] == 0
    assert len(out["ms_a_layer"]) == 8 and U.LANE_BLOCK == 2048
    json.dumps(out)
    xla = U.ssm_state_update_xla

    def touches_the_parked(pool, layer, decay, dtx, b, c, active):
        return xla(pool, layer, decay, dtx, b, c, active * 0 + 1)
    monkeypatch.setattr(U, "ssm_state_update", touches_the_parked)
    with pytest.raises(RuntimeError, match="with first parked"):
        C.ssm_update_phase(seed=C.SEED, slots=6, n=16, hp=256,
                           groups=groups, dtypes=("float32",),
                           lane_blocks=(128,), reps=1)


def test_a_kernel_that_reads_the_wrong_groups_column_is_refused(monkeypatch):
    """What the grouped check is for: a kernel that gives every lane
    group 0's B and C passes with one group and is refused with four."""
    from paddle_tpu.ops.pallas import ssm_update as U
    xla = U.ssm_state_update_xla

    def one_group(pool, layer, decay, dtx, b, c, active):
        return xla(pool, layer, decay, dtx, b[:, :1], c[:, :1], active)
    monkeypatch.setattr(U, "ssm_state_update", one_group)
    kw = dict(seed=C.SEED, slots=4, n=8, hp=128, dtypes=("float32",),
              lane_blocks=(128,), reps=1)
    C.ssm_update_phase(groups=1, **kw)
    with pytest.raises(RuntimeError, match="4 groups, 128 lanes a block"):
        C.ssm_update_phase(groups=4, **kw)


def test_grouped_matmul_phase_checks_both_tiles(monkeypatch):
    """The experts' kernel's chip check at a toy size, under the Pallas
    interpreter, at a width that is no whole lane tile: the decode and
    the prefill row tiles, an expert no row chose, dead tiles; a kernel
    that reads the wrong expert is refused."""
    from paddle_tpu.ops.pallas import grouped_ffn as GF
    monkeypatch.setattr(GF, "_INTERPRET", True)
    out = C.grouped_matmul_phase(seed=C.SEED, experts=4, k=32, n=24,
                                 tiles=((16, 40), (128, 200)), reps=1,
                                 dtype="float32")
    assert out["shape"] == [4, 32, 24] and out["blocks"] == [32, 24]
    assert set(out["checks"]) == {"tile16", "tile128"}
    assert all(c["gap"] < 1e-5 and c["experts_touched"] <= 3
               for c in out["checks"].values())
    assert out["ms_a_call"] > 0 and out["weights_gb_per_s"] >= 0
    json.dumps(out)
    real = GF.grouped_matmul

    def wrong_expert(x, w, emap, live, *, tile_m):
        return real(x, w, (emap + 1) % 4, live, tile_m=tile_m)
    monkeypatch.setattr(GF, "grouped_matmul", wrong_expert)
    with pytest.raises(RuntimeError, match="differs from its XLA form"):
        C.grouped_matmul_phase(seed=C.SEED, experts=4, k=32, n=24,
                               tiles=((16, 40),), reps=1, dtype="float32")


@pytest.mark.parametrize("argv,phase,kw", [
    (["--ssm-update"], "ssm_update_phase", {"groups": 1}),
    (["--ssm-update", "8"], "ssm_update_phase", {"groups": 8}),
    (["--grouped-matmul", "16,2688,1856"], "grouped_matmul_phase",
     {"experts": 16, "k": 2688, "n": 1856}),
    (["--paged-decode", "granite"], "paged_decode_phase",
     C.PAGED_CELLS["granite"])])
def test_main_hands_the_phase_its_shape(monkeypatch, argv, phase, kw):
    seen = {}
    monkeypatch.setattr(C, phase, lambda **k: seen.update(k) or {"phase": 0})
    monkeypatch.setattr(C, "require_tpu", lambda n: jax.devices()[:1])
    monkeypatch.setattr(C, "interpret_is_off", lambda: None)
    monkeypatch.setattr(C, "_ok", lambda devices: 0)
    assert C.main(argv) == 0
    assert seen == dict(kw, seed=C.SEED)


def test_mla_decode_phase_checks_every_context_and_block(monkeypatch):
    """The latent decode kernel's chip check at a toy size, under the
    Pallas interpreter: every context at every block size against the
    dense gather, the rule put back, and a kernel that reads past a
    slot's context refused."""
    from paddle_tpu.ops.pallas import mla_paged_attention as M
    monkeypatch.setattr(M, "_INTERPRET", True)
    monkeypatch.setattr(M, "CHUNK_TOKENS", 8)
    toy = dict(seed=C.SEED, slots=4, heads=4, rank=16, rope=8, page=4,
               width=12, layers=2, contexts=(1, 9, 48), calls=2,
               dtype="float32", tol=1e-5)
    out = C.mla_decode_phase(blocks=(8, 16, 32), **toy)
    keys = [f"block{b}.ctx{c}" for b in (8, 16, 32)
            for c in ("1", "9", "48", "mixed")]
    assert sorted(out["gap"]) == sorted(keys)
    assert sorted(out["ms_a_call"]) == sorted(out["gb_a_s"]) == sorted(keys)
    assert out["gap"]["block8.ctx1"] == 0.0     # one row: its own mean
    assert M.BLOCK_TOKENS == 4096
    json.dumps(out)
    xla = M.mla_paged_attention_xla

    def reads_a_row_too_many(ql, qr, pool, layer, table, lens, **kw):
        return xla(ql, qr, pool, layer, table, lens + 1, **kw)
    monkeypatch.setattr(M, "mla_paged_attention", reads_a_row_too_many)
    with pytest.raises(RuntimeError, match="differs from its XLA form"):
        C.mla_decode_phase(blocks=(16,), **toy)


def test_paged_decode_phase_checks_every_context_block_and_round(monkeypatch):
    """The K/V decode kernel's chip check at a toy size, under the
    Pallas interpreter: every context at every block and round the
    table holds against the dense gather, the rule put back, and a
    kernel that reads past a slot's context refused."""
    from paddle_tpu.ops.pallas import paged_attention as PA
    monkeypatch.setattr(PA, "_INTERPRET", True)
    rule = PA.BLOCK_BYTES, PA.ROUND_TOKENS
    toy = dict(seed=C.SEED, slots=4, kvh=2, rep=4, width=12, layers=2,
               contexts=(1, 9, 48), page=4, calls=3, dtype="float32",
               tol=1e-5)
    out = C.paged_decode_phase(blocks=(8, 16, 32, 64), rounds=(8, 16), **toy)
    swept = [(8, 8), (16, 8), (16, 16), (32, 8), (32, 16)]  # 64 > the table
    keys = [f"block{b}.round{r}.ctx{c}" for b, r in swept
            for c in ("1", "9", "48", "mixed")]
    assert sorted(out["gap"]) == sorted(keys)
    assert sorted(out["ms_a_call"]) == sorted(out["gb_a_s"]) == sorted(keys)
    assert out["gap"]["block8.round8.ctx1"] == 0.0      # one row: itself
    assert out["page_bytes"] == 2 * 4 * 128 * 4
    assert (PA.BLOCK_BYTES, PA.ROUND_TOKENS) == rule
    json.dumps(out)
    xla = PA.paged_attention_xla

    def reads_a_row_too_many(q, k, v, layer, table, lens):
        return xla(q, k, v, layer, table, lens + 1)
    monkeypatch.setattr(PA, "paged_attention", reads_a_row_too_many)
    with pytest.raises(RuntimeError, match="differs from its XLA form"):
        C.paged_decode_phase(blocks=(16,), rounds=(8,), **toy)


def test_interpret_switches_are_checked(monkeypatch):
    from paddle_tpu.ops.pallas import paged_attention
    C.interpret_is_off()
    monkeypatch.setattr(paged_attention, "_INTERPRET", True)
    with pytest.raises(RuntimeError, match="_INTERPRET is on"):
        C.interpret_is_off()


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_no_tpu_no_result(capsys, monkeypatch, cache_config):
    """On the CPU the script exits non-zero and prints no result line."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    with pytest.raises(SystemExit) as e:
        C.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_placed_from_outside(monkeypatch, cache_config):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == was  # nothing set


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path

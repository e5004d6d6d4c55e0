"""Distribution-layer tests: sharding resolver (AbstractMesh, no devices),
pipeline parallelism + multi-pod dry-run cells (subprocess: they need 512
host devices, which must be set before jax initializes)."""
import json
from pathlib import Path

import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.parallel.sharding import batch_sharding, resolve_spec

from conftest import run_forced_devices_subprocess as _run_subprocess

MESH_1POD = AbstractMesh((16, 16), ("data", "model"))
MESH_2POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class TestResolveSpec:
    def test_param_fsdp_tp(self):
        # embedding [vocab, d]: vocab -> model, d -> fsdp(data[, pod])
        spec = resolve_spec((256000, 2048), ("vocab", "fsdp"), MESH_1POD)
        assert spec == P("model", "data")
        spec = resolve_spec((256000, 2048), ("vocab", "fsdp"), MESH_2POD)
        assert spec == P("model", ("pod", "data"))

    def test_divisibility_fallback_replicates(self):
        # kv_heads = 8 does not divide model=16 -> replicated
        spec = resolve_spec((4, 32768, 8, 128),
                            ("batch", "kv_seq", "kv_heads", None), MESH_1POD)
        assert spec[2] is None

    def test_kv_seq_binds_leftover_axis(self):
        # batch=128 takes data; kv_heads=8 cannot take model; kv_seq gets it
        spec = resolve_spec((128, 32768, 8, 128),
                            ("batch", "kv_seq", "kv_heads", None), MESH_1POD)
        assert spec == P("data", "model", None, None)

    def test_context_parallel_batch_one(self):
        # long_500k: batch 1 frees the data axis; kv_heads=4 cannot cover
        # model=16 -> kv_seq claims BOTH (2-D context parallelism)
        spec = resolve_spec((1, 524288, 4, 256),
                            ("batch", "kv_seq", "kv_heads", None), MESH_1POD)
        assert spec[0] is None
        assert spec[1] == ("data", "model")
        assert spec[2] is None

    def test_expert_parallel(self):
        spec = resolve_spec((256, 7168, 2048),
                            ("expert", "fsdp", "mlp"), MESH_2POD)
        assert spec[0] == "model"
        assert spec[1] == ("pod", "data")
        assert spec[2] is None  # model already used by expert

    def test_scalars_and_mismatches_replicate(self):
        assert resolve_spec((), (), MESH_1POD) == P()
        assert resolve_spec((5, 5), ("batch",), MESH_1POD) == P()

    def test_layers_axis_replicated(self):
        spec = resolve_spec((64, 12288, 96, 128),
                            ("layers", "fsdp", "heads", None), MESH_1POD)
        assert spec[0] is None
        assert spec[2] == "model"


class TestBatchSharding:
    """Regression: ``batch_sharding`` used to bind every available mesh
    axis without a divisibility check, handing direct callers invalid
    shardings for non-divisible batch sizes — it now applies the same
    greedy fallback-to-replicate rule as ``resolve_spec``."""

    def test_divisible_binds_all_axes(self):
        assert batch_sharding(MESH_2POD, batch=64).spec == \
            P(("pod", "data"))

    def test_partial_divisibility_binds_prefix(self):
        # 6 % 2 == 0 but 6 % (2*16) != 0: pod binds, data is skipped
        assert batch_sharding(MESH_2POD, batch=6).spec == P("pod")

    def test_indivisible_replicates(self):
        assert batch_sharding(MESH_2POD, batch=5).spec == P(None)
        assert batch_sharding(MESH_1POD, batch=1).spec == P(None)

    def test_no_batch_keeps_legacy_binding(self):
        assert batch_sharding(MESH_2POD).spec == P(("pod", "data"))


class TestPipelineParallel:
    def test_gpipe_matches_sequential(self):
        out = _run_subprocess("""
            import jax, jax.numpy as jnp
            from repro.launch.mesh import make_mesh
            from repro.parallel.pipeline import pipeline_apply
            mesh = make_mesh((4,), ("pod",))
            ws = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16)) * 0.3
            stage_fn = lambda w, x: jnp.tanh(x @ w["w"])
            x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
            out = pipeline_apply(mesh, "pod", stage_fn, {"w": ws}, x,
                                 microbatches=4)
            ref = x
            for i in range(4):
                ref = jnp.tanh(ref @ ws[i])
            print("ERR", float(jnp.max(jnp.abs(out - ref))))
        """, devices=4)
        assert "ERR 0.0" in out


@pytest.mark.slow
class TestDryRunCells:
    """End-to-end lower+compile of production cells (subprocess, 512 devs)."""

    @pytest.mark.parametrize("arch,shape", [("gemma-2b", "decode_32k"),
                                            ("xlstm-350m", "train_4k")])
    def test_single_pod_cell(self, arch, shape, tmp_path):
        out = _run_subprocess(f"""
            import sys
            sys.argv = ["dryrun", "--arch", "{arch}", "--shape", "{shape}",
                        "--single-pod-only", "--out", r"{tmp_path}"]
            from repro.launch import dryrun
            try:
                dryrun.main()
            except SystemExit as e:
                assert e.code == 0, "dry-run failed"
            print("CELL_OK")
        """, devices=512)
        assert "CELL_OK" in out
        rec = json.loads(next(Path(tmp_path).glob("*.json")).read_text())
        assert rec["status"] == "ok"
        assert rec["chips"] == 256
        assert rec["roofline"]["hlo_flops"] > 0

    def test_multi_pod_cell(self, tmp_path):
        out = _run_subprocess(f"""
            import sys
            sys.argv = ["dryrun", "--arch", "gemma-2b", "--shape",
                        "decode_32k", "--multi-pod", "--out", r"{tmp_path}"]
            from repro.launch import dryrun
            try:
                dryrun.main()
            except SystemExit as e:
                assert e.code == 0
            print("CELL_OK")
        """, devices=512)
        assert "CELL_OK" in out
        rec = json.loads(next(Path(tmp_path).glob("*2x16x16.json")).read_text())
        assert rec["status"] == "ok"
        assert rec["chips"] == 512


class TestRooflineParser:
    def test_collective_parsing(self):
        from repro.launch.roofline import parse_collectives
        hlo = """
          %ag = bf16[256,1024]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}
          %ar = f32[128]{0} all-reduce(%y), replica_groups=[32,16]<=[512]
          %cp = bf16[64,64]{1,0} collective-permute(%z)
          %done = f32[8,8]{1,0} all-reduce-done(%ar2)
        """
        stats = parse_collectives(hlo, default_group=256)
        assert stats.counts["all-gather"] == 1
        assert stats.counts["all-reduce"] == 1  # -done not double counted
        assert stats.counts["collective-permute"] == 1
        assert stats.result_bytes["all-gather"] == 256 * 1024 * 2
        assert stats.wire_bytes_per_chip > 0

    def test_roofline_report_terms(self):
        from repro.configs import SHAPES, get_config
        from repro.launch.roofline import analyze
        cfg = get_config("gemma-2b")
        rep = analyze("gemma-2b", "train_4k", "16x16", 256,
                      {"flops": 1e16, "bytes accessed": 1e12}, "", cfg,
                      SHAPES["train_4k"])
        assert rep.compute_s > 0 and rep.memory_s > 0
        assert rep.bottleneck in ("compute", "memory", "collective")
        # synthetic hlo_flops < model_flops here, so only sanity-range
        assert 0 < rep.roofline_fraction <= 2.0
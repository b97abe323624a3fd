"""Test harness config: pin JAX to the CPU with an 8-device virtual
mesh (SURVEY §4). The config.update below must run before any backend
initialization; it is also what makes `mx.tpu()` mean a host device
here (context.py).
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-process spawns, example smoke runs, heavy model "
        "tests — the fast tier is `pytest -m 'not slow'` (<8 min); "
        "the FULL suite remains the snapshot gate")


#: Tier-1 runs inside a wall-clock box (ROADMAP "Tier-1 verify": 870 s).
#: On the installed JAX the suite needs about 1200 s of one 8-core host,
#: a third of it in the tests below: multi-process kill/restart gangs,
#: subprocess fleets, the pipeline / plan / ZeRO parity grids that run
#: again since PR 21 instead of raising at their first line, and the
#: chip_smoke rehearsal (serial seconds measured by PR 21, each >= 6).
#: They run LAST, in their usual relative order, so a boxed run reports
#: on the thousand cheap tests first and spends what is left on these.
#: An unboxed run executes exactly the same tests.
_HEAVY_LAST = {
    "tests/test_anomaly.py::test_subprocess_canary_rollback_on_degraded_worker",
    "tests/test_checkpoint.py::test_kill_restart_sgd_bitexact",
    "tests/test_checkpoint.py::test_kill_restart_zero2_elastic_shards",
    "tests/test_chip_smoke.py::test_compile_cache_can_be_placed_from_outside",
    "tests/test_chip_smoke.py::test_kernels_phase_tiny",
    "tests/test_chip_smoke.py::test_train_serve_multichip_phases_tiny",
    "tests/test_models.py::test_llama_backward_grads_flow_every_param",
    "tests/test_multi_tensor.py::test_compressed_psum_tree_bucketed_matches_leafwise_2bit",
    "tests/test_pipeline.py::test_1f1b_bf16_keeps_loss_and_cotangent_dtype",
    "tests/test_pipeline.py::test_fuzz_1f1b_equals_sequential",
    "tests/test_pipeline.py::test_fuzz_gpipe_equals_sequential",
    "tests/test_pipeline.py::test_gpipe_grad_matches",
    "tests/test_plan.py::test_plan_grid_core",
    "tests/test_quantization.py::test_quantize_net_on_hybridized_net",
    "tests/test_rnn_op.py::test_rnn_shapes_and_grad",
    "tests/test_router.py::test_fleet_local_token_parity_both_replicas",
    "tests/test_router.py::test_fleet_subprocess_failover_trace_and_metrics",
    "tests/test_serving.py::test_fleet_subprocess_kill_failover_zero_lost",
    "tests/test_tp_sp.py::test_ring_attention_exact",
    "tests/test_tp_sp.py::test_ulysses_attention_exact",
    "tests/test_tpu_lowering.py::test_bert_forward_with_flash_lengths_lowers",
    "tests/test_train_loop.py::test_parity_matrix",
    "tests/test_train_loop.py::test_sigkill_resume_on_k_boundary",
    "tests/test_wire_collectives.py::test_eager_weight_gather_parity",
    "tests/test_wire_collectives.py::test_fused_weight_gather_parity",
    "tests/test_zero23.py::test_fused_zero23_composes_with_compression",
    "tests/test_zero23.py::test_fused_zero23_matches_unsharded",
    "tests/test_zero23.py::test_zero_resident_bytes_shrink",
}


def pytest_collection_modifyitems(config, items):
    heavy = [it for it in items
             if it.nodeid.split("[")[0] in _HEAVY_LAST]
    if heavy:
        ids = {id(it) for it in heavy}
        items[:] = [it for it in items if id(it) not in ids] + heavy


# tier-1 regression floor: a FULL-suite run (anything that collected at
# least the floor) must pass at least this many tests. Single-file and
# -k subset runs collect fewer and are exempt. Raise this when the
# suite grows — never lower it.
TIER1_PASSED_FLOOR = 1192


def pytest_sessionfinish(session, exitstatus):
    if session.config.option.collectonly:
        return
    if getattr(session, "testscollected", 0) < TIER1_PASSED_FLOOR:
        return  # subset run, floor does not apply
    passed = getattr(session, "testscollected", 0) - \
        getattr(session, "testsfailed", 0)
    # deselected/skipped tests never ran; only count hard failures
    # against the floor
    if passed < TIER1_PASSED_FLOOR:
        session.exitstatus = 1
        rep = session.config.pluginmanager.get_plugin("terminalreporter")
        if rep is not None:
            rep.write_line(
                f"tier-1 floor violated: {passed} < "
                f"{TIER1_PASSED_FLOOR} passing tests", red=True)

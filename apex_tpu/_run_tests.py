"""Console test entry — the TPU analog of the reference's L0 runner
(reference: tests/L0/run_test.py:20-33, which discovers unittest suites per
area with default inclusions/exclusions and an --xml-report option).

Usage:
    apex-tpu-test                  # run the default suites
    apex-tpu-test amp optimizers   # run selected suites
    apex-tpu-test --list           # show suite names
    apex-tpu-test --xml-report …   # write a junit xml (pytest native)

Suites map to test modules in the repo/sdist ``tests/`` directory; inside an
installed wheel (no tests shipped) point ``--tests-dir`` at a checkout.
"""

import argparse
import os
import sys

# suite name -> test module globs (mirrors run_test.py's TEST_DIRS)
SUITES = {
    "amp": ["test_amp.py", "test_loss_scaler.py"],
    "fp16util": ["test_fp16_utils.py"],
    "optimizers": ["test_fused_optimizers.py", "test_multi_tensor.py",
                   "test_distributed_optimizers.py"],
    "fused_layer_norm": ["test_fused_layer_norm.py",
                         "test_layer_norm_pallas.py"],
    "mlp": ["test_mlp_dense.py"],
    "rnn": ["test_rnn.py"],
    "parallel": ["test_parallel.py", "test_multiproc.py",
                 "test_collectives.py", "test_overlap.py",
                 "test_zero3.py"],
    "transformer": ["test_tensor_parallel.py", "test_pipeline_parallel.py",
                    "test_transformer_models.py", "test_moe.py",
                    "test_context_parallel.py", "test_arguments.py",
                    "test_grad_scaler.py", "test_batch_sampler.py"],
    "contrib": ["test_contrib_basic.py", "test_contrib_attn.py",
                "test_contrib_spatial.py",
                "test_contrib_sparsity_permutation.py"],
    "ops": ["test_ops_attention.py", "test_softmax_pallas.py",
            "test_attention_pallas.py", "test_xent_pallas.py",
            "test_mosaic_block_rules.py", "test_tile_params.py",
            "test_decode_attention_pallas.py",
            "test_decode_attention_mosaic.py", "test_packed_grid.py"],
    "serving": ["test_serving.py", "test_serving_slo.py",
                "test_serving_generation.py",
                "test_serving_resilience.py",
                "test_serving_chaos.py",
                "test_serving_multitok.py",
                "test_serving_tp.py", "test_kv_tier.py",
                "test_router.py", "test_router_chaos.py",
                "test_mimo_serving.py", "test_axk1_serving.py",
                "test_dots3_serving.py", "test_prefill_trunk_rows.py",
                "test_round_halves.py"],
    "api_parity": ["test_api_parity_round3.py"],
    "harness": ["test_run_tests.py", "test_chip_smoke.py",
                "test_compile_cache.py", "test_resilience.py",
                "test_fault_sites.py", "test_import_arrows.py",
                "test_docs_guard.py", "test_apexlint.py"],
    "benchmark": ["test_benchmark_rehearsal_train.py",
                  "test_benchmark_rehearsal_serve.py",
                  "test_span_account.py"],
    "telemetry": ["test_telemetry.py", "test_bench_labels.py",
                  "test_dispatch.py", "test_dispatch_tiles.py",
                  "test_costs.py", "test_window_report.py",
                  "test_spans.py"],
    "api_audit": ["test_noop_knob_audit.py"],
    "checkpoint": ["test_checkpoint.py", "test_checkpoint_durable.py",
                   "test_checkpoint_chaos.py", "test_resume_parity.py"],
    "data": ["test_data.py"],
    "examples": ["test_examples.py"],
}
# reference run_test.py:28-33 excludes run_amp/run_fp16util by default;
# here every suite is cheap enough to include except the example smokes
DEFAULT_EXCLUDE = {"examples"}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("suites", nargs="*",
                   help="suite names (default: all except "
                        f"{sorted(DEFAULT_EXCLUDE)})")
    p.add_argument("--list", action="store_true", help="list suites")
    p.add_argument("--tests-dir", default=None,
                   help="directory containing the test modules "
                        "(default: <repo>/tests next to the package)")
    p.add_argument("--xml-report", default=None, metavar="PATH",
                   help="write a junit xml report")
    p.add_argument("--slow", action="store_true",
                   help="include the slow-marked end-to-end smokes "
                        "(deselected by default via pyproject addopts)")
    args, pytest_extra = p.parse_known_args(argv)

    if args.list:
        for name, mods in SUITES.items():
            print(f"{name}: {' '.join(mods)}")
        return 0

    tests_dir = args.tests_dir
    if tests_dir is None:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        tests_dir = os.path.join(repo, "tests")
    if not os.path.isdir(tests_dir):
        print(f"tests directory not found: {tests_dir} "
              "(installed wheel? pass --tests-dir <checkout>/tests)",
              file=sys.stderr)
        return 2

    names = args.suites or [s for s in SUITES if s not in DEFAULT_EXCLUDE]
    unknown = [n for n in names if n not in SUITES]
    if unknown:
        print(f"unknown suites: {unknown}; --list shows options",
              file=sys.stderr)
        return 2

    paths = [os.path.join(tests_dir, m) for n in names for m in SUITES[n]]
    paths = [p_ for p_ in paths if os.path.exists(p_)]

    import pytest

    pytest_args = ["-q", *paths, *pytest_extra]
    if args.slow:
        pytest_args += ["-m", ""]  # clear the 'not slow' default selection
    if args.xml_report:
        pytest_args.append(f"--junitxml={args.xml_report}")
    return pytest.main(pytest_args)


if __name__ == "__main__":
    sys.exit(main())

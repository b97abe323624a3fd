"""BERT-base pretraining throughput (SURVEY §6: samples/sec).

Standalone wrapper over bench.py's `_bert_phase` (fused fwd+bwd+AdamW
step, bf16, ragged valid_length so the Pallas flash-attention kernel
engages). Like bench.py it measures on the chip or not at all, in the
calling process: a failure or the BENCH_BUDGET_S deadline ends it with
a non-zero exit code. bench.py also folds this metric into its own
headline JSON as `bert_samples_per_sec`; this script exists for a
focused, full-budget BERT run.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import REFERENCE_BERT_SPS, _bert_phase, _best, _guard


def main():
    import jax

    from mxnet_tpu import tracing

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit("bert_bench.py measures on the chip: "
                         f"jax.default_backend() is {backend!r}")
    _guard.best.update({
        "metric": "bert_base_pretrain_samples_per_sec_per_chip",
        "unit": "samples/sec",
    })
    _guard.install()
    tracing.enable_compile_cache()
    _best.update({"backend": backend, "phase": "backend_acquired"})
    sps = _bert_phase(backend)
    _best.update({
        "value": round(sps, 2),
        "vs_baseline": round(sps / REFERENCE_BERT_SPS, 3),
        "phase": "bert_pretrain",
    })
    _guard.emit()


if __name__ == "__main__":
    main()

"""Generate the per-class API reference tree under docs/api/.

The reference ships an 83-page markdown API tree
(`docs/mkdocs.yml`: KerasStyleAPIGuide per-layer pages, APIGuide per
subsystem). Here the reference pages are GENERATED from the live
docstrings — the docs cannot drift from the code, and the
``tests/test_api_docs.py`` walk fails the build when a public entry is
missing from the tree or undocumented.

Run: ``python scripts/gen_api_docs.py`` (writes docs/api/*.md; commit
the output). Deterministic: pages follow each module's ``__all__``
order.
"""

from __future__ import annotations

import inspect
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

# page slug -> (title, blurb, [module paths]) — the public import surface.
# Every module listed here is walked by tests/test_api_docs.py; adding a
# module there without regenerating fails CI.
PAGES = {
    "keras-layers-core": (
        "Keras layers — core",
        "Dense/embedding/dropout/reshape and friends "
        "(ref KerasStyleAPIGuide/Layers/core.md).",
        ["analytics_zoo_tpu.keras.layers.core"]),
    "keras-layers-convolutional": (
        "Keras layers — convolutional",
        "Conv 1D/2D/3D, transposed, separable, up/down-sampling "
        "(ref KerasStyleAPIGuide/Layers/convolutional.md).",
        ["analytics_zoo_tpu.keras.layers.convolutional"]),
    "keras-layers-recurrent": (
        "Keras layers — recurrent",
        "LSTM/GRU/SimpleRNN/ConvLSTM2D, scan-based "
        "(ref KerasStyleAPIGuide/Layers/recurrent.md).",
        ["analytics_zoo_tpu.keras.layers.recurrent"]),
    "keras-layers-normalization": (
        "Keras layers — normalization and embedding",
        "BatchNorm/LayerNorm/Embedding "
        "(ref KerasStyleAPIGuide/Layers/normalization.md, embedding.md).",
        ["analytics_zoo_tpu.keras.layers.normalization",
         "analytics_zoo_tpu.keras.layers.embeddings"]),
    "keras-layers-attention": (
        "Keras layers — attention and transformers",
        "TransformerLayer/BERT blocks, sequence- and pipeline-parallel "
        "attention (ref APIGuide/PipelineAPI/keras-api transformer rows); "
        "the decoder block library (grouped-query and latent attention, "
        "gated short convolution, the Mamba-2 mixer, rotary embedding, "
        "SwiGLU, DecoderBlock).",
        ["analytics_zoo_tpu.keras.layers.attention",
         "analytics_zoo_tpu.keras.layers.decoder"]),
    "keras-layers-extras": (
        "Keras layers — wrappers and extras",
        "TimeDistributed/Bidirectional, merges, noise, masking and the "
        "elementwise tail (ref KerasStyleAPIGuide/Layers/*.md tail).",
        ["analytics_zoo_tpu.keras.layers.extras",
         "analytics_zoo_tpu.keras.layers.crf",
         "analytics_zoo_tpu.keras.layers.moe"]),
    "keras-engine": (
        "Keras engine — Sequential / Model / topology",
        "Model assembly, compile/fit/evaluate/predict, freeze, "
        "save/load (ref KerasStyleAPIGuide/keras-api.md).",
        ["analytics_zoo_tpu.keras.engine.topology",
         "analytics_zoo_tpu.keras.engine.base"]),
    "keras-objectives": (
        "Objectives (losses)",
        "The 16 training objectives (ref APIGuide/Losses.md).",
        ["analytics_zoo_tpu.keras.objectives"]),
    "keras-metrics": (
        "Metrics",
        "Validation metrics (ref APIGuide/Metrics.md).",
        ["analytics_zoo_tpu.keras.metrics"]),
    "keras-optimizers": (
        "Optimizers and schedules",
        "Optimizers + LR schedules (ref APIGuide/OptimPart.md).",
        ["analytics_zoo_tpu.keras.optimizers"]),
    "keras-regularizers": (
        "Regularizers",
        "L1/L2 weight regularizers (ref keras regularizers).",
        ["analytics_zoo_tpu.keras.regularizers"]),
    "keras-datasets": (
        "Bundled dataset helpers",
        "mnist/imdb/boston_housing/reuters offline loaders "
        "(ref pyzoo keras datasets).",
        ["analytics_zoo_tpu.keras.datasets"]),
    "keras2": (
        "keras2 API",
        "The keras-2 style layer surface (ref zoo.pipeline.api.keras2).",
        ["analytics_zoo_tpu.keras2.layers"]),
    "autograd": (
        "autograd",
        "Variable/Parameter/Lambda/CustomLoss and the op table "
        "(ref APIGuide/PipelineAPI/autograd.md).",
        ["analytics_zoo_tpu.autograd"]),
    "data-feature-set": (
        "FeatureSet and device caching",
        "Array/DeviceCached/Pair/Transformed feature sets — the input "
        "pipeline (ref APIGuide/FeatureEngineering/featureset.md).",
        ["analytics_zoo_tpu.data.feature_set"]),
    "data-image": (
        "Image pipeline",
        "ImageSet + the ~30 image transformers "
        "(ref APIGuide/FeatureEngineering/image.md).",
        ["analytics_zoo_tpu.data.image_set"]),
    "data-image3d": (
        "3D image pipeline",
        "3D crop/rotate/affine transformers "
        "(ref APIGuide/FeatureEngineering/image3d.md).",
        ["analytics_zoo_tpu.data.image3d"]),
    "data-text": (
        "Text pipeline and relations",
        "TextSet transformers + Relations "
        "(ref APIGuide/FeatureEngineering/text.md, relation.md).",
        ["analytics_zoo_tpu.data.text_set"]),
    "data-pipeline": (
        "Streaming input pipeline",
        "Pipeline sources/stages: parallel transform workers, async "
        "device prefetch, checkpointable iterators "
        "(docs/data-pipeline.md).",
        ["analytics_zoo_tpu.data.pipeline",
         "analytics_zoo_tpu.data.sources"]),
    "batch": (
        "Batch scoring — resumable sharded batch-predict",
        "Offline batch-predict jobs: the pipelined score loop, atomic "
        "sharded output (manifest + CRC32 + COMMIT), and the resumable "
        "job runner with kill→resume bitwise identity "
        "(docs/batch-scoring.md).",
        ["analytics_zoo_tpu.batch.job",
         "analytics_zoo_tpu.batch.writers",
         "analytics_zoo_tpu.batch.runner"]),
    "engine-estimator": (
        "Estimator (training engine)",
        "The SPMD training loop: train/evaluate/predict, ZeRO-1, "
        "chunked/fused dispatch, watchdog "
        "(ref ProgrammingGuide/estimator.md).",
        ["analytics_zoo_tpu.engine.estimator",
         "analytics_zoo_tpu.engine.triggers"]),
    "engine-checkpoint": (
        "Checkpoint and summaries",
        "Checkpoint save/restore + TensorBoard event writing "
        "(ref ProgrammingGuide/visualization.md).",
        ["analytics_zoo_tpu.engine.checkpoint",
         "analytics_zoo_tpu.engine.summary"]),
    "ft": (
        "Fault tolerance — atomic checkpoints, preemption, hot-reload",
        "Async CheckpointManager over the tmp-dir/rename/COMMIT protocol, "
        "retention, SIGTERM save-then-exit, chaos failure points, and the "
        "serving checkpoint watcher (docs/fault-tolerance.md).",
        ["analytics_zoo_tpu.ft.manager",
         "analytics_zoo_tpu.ft.atomic",
         "analytics_zoo_tpu.ft.preemption",
         "analytics_zoo_tpu.ft.hot_reload",
         "analytics_zoo_tpu.ft.chaos"]),
    "ft-distributed": (
        "Multi-host training — psum step, sharded optimizer, "
        "two-phase commit",
        "DistContext filesystem rendezvous, ShardedUpdater (1/N "
        "optimizer slices), and commit_sharded_checkpoint — the "
        "N-writer extension of the atomic protocol "
        "(docs/distributed-training.md, docs/fault-tolerance.md).",
        ["analytics_zoo_tpu.ft.distributed"]),
    "nncontext": (
        "NNContext and configuration",
        "Mesh/runtime bootstrap (ref APIGuide/PipelineAPI/nnframes.md "
        "init_nncontext).",
        ["analytics_zoo_tpu.common.nncontext",
         "analytics_zoo_tpu.common.config",
         "analytics_zoo_tpu.common.runtime"]),
    "profiling": (
        "Profiling and tracing",
        "set_profile + xplane summaries (ref ProgrammingGuide).",
        ["analytics_zoo_tpu.common.profiling",
         "analytics_zoo_tpu.common.trace_tools"]),
    "observability": (
        "Observability — spans, metrics, compile accounting",
        "The unified layer: span tracing with Chrome-trace export, the "
        "labeled metrics registry with Prometheus exposition, and "
        "jax.monitoring compile counters (docs/observability.md).",
        ["analytics_zoo_tpu.common.observability"]),
    "nnframes": (
        "nnframes — DataFrame ML pipeline",
        "NNEstimator/NNModel/NNClassifier/NNImageReader "
        "(ref APIGuide/PipelineAPI/nnframes.md).",
        ["analytics_zoo_tpu.nnframes"]),
    "inference": (
        "InferenceModel and serving export",
        "do_load*/do_quantize/do_calibrate/do_predict + the C serving "
        "shim export (ref APIGuide/PipelineAPI/inference.md).",
        ["analytics_zoo_tpu.inference.inference_model",
         "analytics_zoo_tpu.inference.serving_export"]),
    "pipeline": (
        "Pipeline parallelism — MPMD stage axis",
        "StagePlan layer partitioning, 1F1B/GPipe microbatch schedules, "
        "activation-slot leases and the pipelined trainer with "
        "stage-owned sharded checkpoints (docs/pipeline-parallel.md).",
        ["analytics_zoo_tpu.pipeline.plan",
         "analytics_zoo_tpu.pipeline.schedule",
         "analytics_zoo_tpu.pipeline.buffers",
         "analytics_zoo_tpu.pipeline.trainer"]),
    "mesh": (
        "Sharded inference mesh",
        "MeshConfig + ShardingPlan: the declarative mesh layer the "
        "serving/batch engines consume to serve models bigger than one "
        "device (docs/sharded-inference.md).",
        ["analytics_zoo_tpu.mesh.config",
         "analytics_zoo_tpu.mesh.plan"]),
    "serving": (
        "Online serving engine",
        "ServingEngine/DynamicBatcher/metrics/HTTP frontend — dynamic "
        "batching onto AOT-compiled bucket shapes "
        "(ref ClusterServingGuide; docs/serving.md tier 2).",
        ["analytics_zoo_tpu.serving.engine",
         "analytics_zoo_tpu.serving.batcher",
         "analytics_zoo_tpu.serving.metrics",
         "analytics_zoo_tpu.serving.http"]),
    "serving-sequence": (
        "Sequence serving",
        "Length-bucketed prefill + iteration-level continuous batching "
        "for autoregressive decode: fixed-capacity slot array, "
        "preallocated per-slot carries, bounded prefill staging "
        "(docs/serving.md 'Sequence serving').",
        ["analytics_zoo_tpu.serving.sequence",
         "analytics_zoo_tpu.serving.decode_state"]),
    "serving-resilience": (
        "Serving resilience",
        "Admission control, circuit breaker, flush-thread watchdog and "
        "graceful drain for the online engine (docs/resilience.md).",
        ["analytics_zoo_tpu.serving.resilience"]),
    "serving-result-cache": (
        "Serving result cache",
        "Content-addressed inference result cache: SHA-256 keys over "
        "(model, routed version, canonical input bytes), LRU+TTL+byte "
        "budget, single-flight coalescing, copy-on-write hit views "
        "(docs/result-cache.md).",
        ["analytics_zoo_tpu.serving.result_cache"]),
    "serving-frontdoor": (
        "Serving front door (horizontal tier)",
        "Preforked multi-process front door: N engine workers behind a "
        "consistent-hash ring, transparent retry + respawn on worker "
        "death, rolling drain, single-authority quota, merged /metrics "
        "(docs/serving.md 'Horizontal scaling').",
        ["analytics_zoo_tpu.serving.frontdoor",
         "analytics_zoo_tpu.serving.worker"]),
    "serving-fabric": (
        "Serving fleet fabric (multi-host tier)",
        "Multi-host serving: filesystem-rendezvous membership with "
        "epoch-numbered views, cross-host sticky routing, replicated "
        "admin/quota, the cooperative result cache's tree codec + peer "
        "client, and queue-depth worker autoscaling (docs/fleet.md).",
        ["analytics_zoo_tpu.serving.fabric.membership",
         "analytics_zoo_tpu.serving.fabric.door",
         "analytics_zoo_tpu.serving.fabric.coopcache",
         "analytics_zoo_tpu.serving.fabric.autoscaler"]),
    "serving-router": (
        "Serving deployment control plane",
        "Weighted version routing with sticky keys, staged canary "
        "rollouts with auto-promote/auto-rollback, shadow traffic and "
        "per-tenant quotas (docs/rollouts.md).",
        ["analytics_zoo_tpu.serving.router",
         "analytics_zoo_tpu.serving.rollout",
         "analytics_zoo_tpu.serving.quota"]),
    "flywheel": (
        "Online-learning flywheel",
        "The capture → replay → incremental retrain → canary promotion "
        "loop: sampled request/response capture on the serving path, "
        "committed segments as a training Source, warm-start retrains "
        "with a crash-safe consumption high-water mark, and the "
        "promotion controller with quarantine-on-rollback "
        "(docs/flywheel.md).",
        ["analytics_zoo_tpu.flywheel.capture",
         "analytics_zoo_tpu.flywheel.replay",
         "analytics_zoo_tpu.flywheel.trainer",
         "analytics_zoo_tpu.flywheel.controller"]),
    "net": (
        "Net — foreign model loaders",
        "load_onnx/load_tf/load_keras/load_caffe/load_torch "
        "(ref APIGuide/PipelineAPI/net.md).",
        ["analytics_zoo_tpu.net"]),
    "tfnet": (
        "TFNet — frozen-graph import",
        "GraphDef -> jnp interpreter (ref APIGuide/TFPark/tfnet).",
        ["analytics_zoo_tpu.tfnet"]),
    "onnx": (
        "ONNX importer",
        "The 44-op ONNX loader (ref ONNX support list).",
        ["analytics_zoo_tpu.onnx"]),
    "tfpark": (
        "TFPark — TFDataset / KerasModel / TFEstimator",
        "The tf.keras interop surface (ref APIGuide/TFPark/*).",
        ["analytics_zoo_tpu.tfpark"]),
    "tfpark-text": (
        "TFPark text models",
        "NER/SequenceTagger/IntentEntity over the CRF "
        "(ref APIGuide/TFPark/text-models.md).",
        ["analytics_zoo_tpu.tfpark.text"]),
    "models-image-classification": (
        "Model zoo — image classification",
        "The 10-arch catalog + pretrained flow "
        "(ref ProgrammingGuide/image-classification.md).",
        ["analytics_zoo_tpu.models.image.imageclassification"]),
    "models-object-detection": (
        "Model zoo — object detection",
        "SSD/FRCNN, NMS, evaluators (ref ProgrammingGuide/"
        "object-detection.md).",
        ["analytics_zoo_tpu.models.image.objectdetection"]),
    "models-recommendation": (
        "Model zoo — recommendation",
        "NeuralCF/WideAndDeep/SessionRecommender "
        "(ref APIGuide/Models/recommendation.md).",
        ["analytics_zoo_tpu.models.recommendation"]),
    "models-text": (
        "Model zoo — text",
        "TextClassifier/KNRM/Seq2seq (ref APIGuide/Models/*.md).",
        ["analytics_zoo_tpu.models.textclassification",
         "analytics_zoo_tpu.models.textmatching",
         "analytics_zoo_tpu.models.seq2seq"]),
    "models-anomaly": (
        "Model zoo — anomaly detection",
        "AnomalyDetector (ref APIGuide/Models/anomaly-detection.md).",
        ["analytics_zoo_tpu.models.anomalydetection"]),
    "parallel": (
        "Parallelism — sharding, ring attention, pipeline, MoE",
        "The TPU-native distributed backbone "
        "(SURVEY §2.4; the reference's NCCL/MPI analogue).",
        ["analytics_zoo_tpu.parallel.sharding",
         "analytics_zoo_tpu.parallel.ring_attention",
         "analytics_zoo_tpu.parallel.pipeline",
         "analytics_zoo_tpu.parallel.moe"]),
    "ops": (
        "Ops — attention, flash kernels, q/k norm and rotary, SSD scan, bbox",
        "The hot-op layer: dispatchered attention, the Pallas flash "
        "kernels, the fused q/k head norm and rotary with the rotary spec "
        "they read (YaRN among them), the chunked state-space scan, padded "
        "NMS (SURVEY §2.3).",
        ["analytics_zoo_tpu.ops.attention",
         "analytics_zoo_tpu.ops.flash_attention",
         "analytics_zoo_tpu.ops.rotary",
         "analytics_zoo_tpu.ops.qk_rotary",
         "analytics_zoo_tpu.ops.ssd",
         "analytics_zoo_tpu.ops.bbox"]),
}


def _public_names(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in vars(mod)
                 if not n.startswith("_")
                 and getattr(getattr(mod, n), "__module__", None)
                 == mod.__name__]
    return [n for n in names if not inspect.ismodule(getattr(mod, n, None))]


def _signature(obj) -> str:
    try:
        if inspect.isclass(obj):
            sig = inspect.signature(obj.__init__)
            params = list(sig.parameters.values())[1:]  # drop self
            sig = sig.replace(parameters=params)
        else:
            sig = inspect.signature(obj)
        return str(sig)
    except (TypeError, ValueError):
        return "(...)"


def _doc(obj) -> str:
    d = inspect.getdoc(obj)
    return d.strip() if d else ""


def _methods(cls):
    """Public methods defined BY this class. An undocumented OVERRIDE of a
    base-class method is skipped — the base's docstring states the
    protocol (build/call/apply on every layer) — but an undocumented NEW
    public method renders *(undocumented)* so the test fails on it."""
    out = []
    for name, member in sorted(vars(cls).items()):
        if name.startswith("_"):
            continue
        if isinstance(member, property):
            fn, sig = member.fget, "  # property"
        else:
            fn = member.__func__ if isinstance(
                member, (classmethod, staticmethod)) else member
            if not (inspect.isfunction(fn) or inspect.ismethod(fn)):
                continue
            sig = None
        doc = _doc(fn)
        if not doc and any(hasattr(base, name) for base in cls.__mro__[1:]):
            continue
        out.append((name, sig if sig is not None else _signature(fn), doc))
    return out


def render_page(slug, title, blurb, modules) -> str:
    import importlib

    lines = [f"# {title}", "", blurb, ""]
    seen = set()
    for mpath in modules:
        mod = importlib.import_module(mpath)
        for name in _public_names(mod):
            if name in seen:
                continue
            obj = getattr(mod, name, None)
            if obj is None or inspect.ismodule(obj):
                continue
            seen.add(name)
            kind = "class" if inspect.isclass(obj) else (
                "function" if callable(obj) else "value")
            lines.append(f"## {name}")
            lines.append("")
            if callable(obj):
                lines.append(f"```python\n{name}{_signature(obj)}\n```")
                lines.append("")
            doc = _doc(obj)
            lines.append(doc if doc else "*(undocumented)*")
            lines.append("")
            if kind == "class":
                for mname, msig, mdoc in _methods(obj):
                    lines.append(f"### {name}.{mname}")
                    lines.append("")
                    lines.append(f"```python\n{mname}{msig}\n```")
                    lines.append("")
                    lines.append(mdoc if mdoc else "*(undocumented)*")
                    lines.append("")
            lines.append(f"*Import:* `from {mpath} import {name}`")
            lines.append("")
    return "\n".join(lines)


def main(out_dir=None):
    import jax

    jax.config.update("jax_platforms", "cpu")  # never touch the accelerator
    out_dir = out_dir or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "docs", "api")
    os.makedirs(out_dir, exist_ok=True)
    index = ["# API reference", "",
             "Generated from the live docstrings by "
             "`scripts/gen_api_docs.py` — regenerate after changing any "
             "public API (`tests/test_api_docs.py` fails on drift).", ""]
    n_entries = 0
    for slug, (title, blurb, modules) in PAGES.items():
        page = render_page(slug, title, blurb, modules)
        with open(os.path.join(out_dir, f"{slug}.md"), "w") as f:
            f.write(page)
        n = page.count("\n## ")
        n_entries += n
        index.append(f"- [{title}]({slug}.md) — {n} entries")
    with open(os.path.join(out_dir, "README.md"), "w") as f:
        f.write("\n".join(index) + "\n")
    print(f"wrote {len(PAGES)} pages, {n_entries} entries -> {out_dir}")


if __name__ == "__main__":
    main()

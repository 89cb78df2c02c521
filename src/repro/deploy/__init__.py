"""repro.deploy — the QIR -> Pallas dataflow compiler and scenario runtime.

Closes the paper's loop: quantization-aware training exports a QIR graph
(``core.qir``: ``export_qmlp`` for the MLPs, ``export_qcnn`` for the conv
nets), this package streamlines and fuses it into integer dataflow stages
(``lower``), compiles the stage schedule into one jit program plus a
segment-compiled streaming pipeline whose FIFO depths come from the
paper's FIFO pass (``executor``), and measures it under the MLPerf Tiny
load scenarios (``scenarios``). See ``docs/pipeline.md`` for the
streaming architecture and ``docs/lowering.md`` for the
stage/bit-exactness contract.

What actually lowers to fused integer stages:

  * ``Dense  -> [BatchNorm] -> Relu -> Quant``  -> multi-threshold matmul
  * ``Conv2D -> [BatchNorm] -> Relu -> Quant``  -> fused direct-conv kernel
    (implicit im2col, thresholds in-register; ``conv_lowering="im2col"`` or
    REPRO_CONV_LOWERING=im2col falls back to patch-matrix + threshold_matmul)
  * ``Dense|Conv2D -> Quant(bipolar)``          -> single-threshold sign bank
    (the binary CNV path)
  * ``MaxPool`` / ``Flatten``                   -> integer pool / reshape
  * a trailing ``Dense``                        -> float logits head

Anything else falls back to a float per-node reference chain, so every
exported graph runs — just not fused.

    graph = export_qcnn(model, params, calibrate=x_cal)
    model = compile_graph(graph, in_scale=graph.meta["in_scale"])
    logits = model.offline(x_int)                     # MLPerf Offline
    reports = run_all_scenarios(model.offline, mk,    # the LoadGen sweep
                                compiled=model)       # + per-stage latency
"""

from repro.deploy.executor import (  # noqa: F401
    CompiledJaxModel,
    CompiledTinyModel,
    DEFAULT_MICRO_BATCH,
    StreamingStats,
    compile_graph,
)
from repro.deploy.lower import (  # noqa: F401
    CONV_LOWERINGS,
    ChainMatch,
    ConvGeom,
    default_conv_lowering,
    FlattenStage,
    FloatHeadStage,
    FusedConvThresholdStage,
    FusedThresholdStage,
    IntPoolStage,
    MegakernelSegment,
    RefChainStage,
    Segment,
    StageSchedule,
    group_segments,
    im2col,
    lower_graph,
    plan_megakernel,
    stage_for,
)
from repro.deploy.scenarios import (  # noqa: F401
    ScenarioReport,
    multi_stream,
    offline,
    run_all_scenarios,
    server_poisson,
    server_streaming,
    single_stream,
    streaming_pipeline,
)

"""Heimdall AI assistant (ref: /root/reference/pkg/heimdall/)."""

from nornicdb_tpu.heimdall.context import (
    CYPHER_PRIMER,
    GenerateParams,
    PromptContext,
    PromptExample,
    TokenBudget,
    estimate_tokens,
)
from nornicdb_tpu.heimdall.manager import (
    Bifrost,
    EngineGenerator,
    Generator,
    HeimdallManager,
    HeimdallMetrics,
    QwenGenerator,
    TemplateGenerator,
    WeightsGenerator,
)
from nornicdb_tpu.heimdall.registry import (
    MODEL_CLASSIFICATION,
    MODEL_EMBEDDING,
    MODEL_REASONING,
    DatabaseEvent,
    EventDispatcher,
    MetricsRegistry,
    ModelInfo,
    ModelRegistry,
)

__all__ = [
    "Bifrost", "EngineGenerator", "Generator", "HeimdallManager",
    "HeimdallMetrics", "QwenGenerator", "TemplateGenerator",
    "WeightsGenerator",
    "PromptContext", "PromptExample", "TokenBudget", "GenerateParams",
    "CYPHER_PRIMER", "estimate_tokens",
    "ModelInfo", "ModelRegistry", "MetricsRegistry",
    "DatabaseEvent", "EventDispatcher",
    "MODEL_EMBEDDING", "MODEL_REASONING", "MODEL_CLASSIFICATION",
]

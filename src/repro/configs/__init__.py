"""The chordality service's configuration: ``ServiceConfig`` and its presets."""
from repro.configs.service import (
    SERVICE_CONFIGS,
    AutotuneConfig,
    ServiceConfig,
    service_config,
)

__all__ = [
    "SERVICE_CONFIGS",
    "AutotuneConfig",
    "ServiceConfig",
    "service_config",
]

from repro.configs.base import (
    FederatedConfig, ModelConfig, MoEConfig, RunConfig, model_config_from_dict,
    model_config_to_dict, normalize_model_kwargs, reduced,
)
from repro.configs.registry import ALL_ARCHS, all_configs, get_config

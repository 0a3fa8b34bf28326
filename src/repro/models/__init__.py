from repro.models.registry import get_model, param_count, param_shapes_and_axes

"""Per model type: how a configuration file becomes the program's
``ModelConfig``. One file per ``model.model_type``."""

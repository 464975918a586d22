"""Device ms a decode step spends quantizing and gathering AxO activations
(scopes ``axo.quantize`` + ``axo.gather``)."""

from scopes import axo_glue_ms_per_step as read  # noqa: F401

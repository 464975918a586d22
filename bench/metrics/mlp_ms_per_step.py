"""Device ms a decode step spends in the MLP (scope ``mlp``)."""

from scopes import mlp_ms_per_step as read  # noqa: F401

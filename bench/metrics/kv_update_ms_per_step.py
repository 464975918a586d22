"""Device ms a decode step spends writing the KV cache (scope
``attn.kv_update``)."""

from scopes import kv_update_ms_per_step as read  # noqa: F401

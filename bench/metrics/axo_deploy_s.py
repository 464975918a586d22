"""Host seconds of the program's last ``axo.deploy`` span: the AxO
deployment's share of set-up."""

from scopes import axo_deploy_s as read  # noqa: F401

"""Deployment registry: one module per detector deployment.

Importing this package registers every deployment under its ``--config <id>``.
"""
from repro.configs import lartpc_uboone  # noqa: F401

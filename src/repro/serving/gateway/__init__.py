"""Async serving gateway — the fleet's streaming front door.

Layering (each importable on its own):

  core.GatewayCore        synchronous front-door state machine: typed
                          admission, overload shedding, event delivery,
                          rolling weight hot-swap over a PoolFleet
  admission.OverloadPolicy  shed-before-tick policy (feasibility + depth)
  registry.ModelRegistry  resident/staged checkpoints with versions
  bridge.EngineBridge     the one engine thread pumping the core +
                          a command queue (asyncio-safe call/acall)
  http                    aiohttp HTTP/SSE transport

See docs/gateway.md for endpoints, the SSE event schema, the overload
policy, and the hot-swap walkthrough.
"""
from .admission import OverloadPolicy
from .bridge import EngineBridge
from .core import GatewayCore, parse_spec
from .http import build_app, start_gateway, stop_gateway
from .registry import ModelRegistry

__all__ = ["EngineBridge", "GatewayCore", "ModelRegistry",
           "OverloadPolicy", "build_app", "parse_spec", "start_gateway",
           "stop_gateway"]

"""Shared wire-level constants (reference: provisioning/constants.py —
ports, labels, timeouts). One definition so the pod server, controller, CLI,
and client config can never drift apart."""

DEFAULT_SERVER_PORT = 32300

# Serving front-door headers (ISSUE 9). Defined here — not in
# serving/router.py or serve/sessions.py — because the two halves of
# affinity routing live in DIFFERENT processes (the pod HTTP server routes;
# the rank worker's engine holds the resident prefixes) and must agree on
# the wire names without importing each other's runtimes.
SESSION_HEADER = "X-KT-Session"
PRIORITY_HEADER = "X-KT-Priority"

# /ready?wait=<seconds>: the longest a pod holds a deploying client's
# request open while its answer is "not yet", and so what that client asks
# for. Well under the 600 s of the controller's proxy route, through which
# a client without a service_url reaches the pod.
READY_WAIT_CAP_S = 10.0


def server_port(value: "str | int | None" = None) -> int:
    """The ONE tolerant KT_SERVER_PORT parse, shared by the pod server, the
    controller WebSocket registration, and the CLI. Empty or malformed values
    (e.g. ``KT_SERVER_PORT=""`` from a BYO manifest, or ``"auto"``) warn and
    fall back to the default instead of crashing the pod at startup or
    silently looping in the WS reconnect."""
    import logging
    import os

    raw = os.environ.get("KT_SERVER_PORT") if value is None else value
    if raw is None or raw == "":
        return DEFAULT_SERVER_PORT
    try:
        return int(raw)
    except (TypeError, ValueError):
        logging.getLogger(__name__).warning(
            "invalid KT_SERVER_PORT=%r; using default %d",
            raw, DEFAULT_SERVER_PORT)
        return DEFAULT_SERVER_PORT


# Env vars that define ONE process's pod identity or wiring. They must never
# leak from a spawning process into a daemon or a DIFFERENT pod: a controller
# accidentally started from inside a pod (unguarded user driver code) would
# otherwise stamp every future pod with the dead pod's service name, module
# pointers, and — worst — a stale KT_DATA_STORE_URL, poisoning code sync
# long after the original pod is gone.
POD_IDENTITY_ENV = (
    "POD_NAME", "POD_IP", "POD_IPS", "LOCAL_IPS",
    "KT_POD_NAME", "KT_LAUNCH_ID", "KT_SERVICE_NAME", "KT_NAMESPACE",
    "KT_MODULE_NAME", "KT_FILE_PATH", "KT_CLS_OR_FN_NAME",
    "KT_CALLABLE_TYPE", "KT_PROJECT_ROOT", "KT_INIT_ARGS",
    "KT_DISTRIBUTED_CONFIG", "KT_DOCKERFILE", "KT_APP_CMD",
    "KT_DATA_STORE_URL", "KT_API_URL", "KT_SERVER_PORT",
)

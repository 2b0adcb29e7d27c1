"""One attribute of the run's ``client.deploy`` span, or the sum of
several: the boot phases the pod reported in its last ``/ready`` and
``poll_slack_s``, how long the service had been ready when the deploying
client noticed."""

import span_ring


def read(ctx, attrs, scale=1.0, ring=None):
    deploy = span_ring.last_deploy(ring)
    if deploy is None:
        return None
    value = span_ring.total(deploy, attrs)
    return None if value is None else scale * value

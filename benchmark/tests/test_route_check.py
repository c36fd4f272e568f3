"""The statement-level check that a statement ran on the device path of
its configuration, over what GET /v1/query/{id} can say."""

import pytest

import run as harness

DISTRIBUTED = {"distributed": True, "route": None, "fallbackReason": None}
LOCAL_DEVICE = {"distributed": False, "route": "device",
                "fallbackReason": "no active workers"}


@pytest.mark.parametrize("info,has_workers,ok", [
    (DISTRIBUTED, True, True),
    (LOCAL_DEVICE, False, True),
    # the scheduler declined and the coordinator ran it, on its device
    # or not: in a worker deployment that is not the path under test
    (dict(LOCAL_DEVICE, fallbackReason="not eligible"), True, False),
    # the silent re-run after a worker task failed or timed out
    (dict(LOCAL_DEVICE, fallbackReason="task failure: timed out"),
     True, False),
    (dict(LOCAL_DEVICE, fallbackReason="task failure: x"), False, False),
    # the host interpreter, a cache, a micro-batch
    (dict(LOCAL_DEVICE, route="host"), False, False),
    (dict(LOCAL_DEVICE, route="cache"), False, False),
    (dict(LOCAL_DEVICE, route="microbatch"), False, False),
    (dict(LOCAL_DEVICE, route=None), False, False),
    # a single-node cell that found a worker is another deployment
    (DISTRIBUTED, False, False),
])
def test_on_device(info, has_workers, ok):
    assert harness.on_device(info, has_workers) is ok

"""Deployment kinds: what a configuration's ``deployment_kind`` names.

A cell is found by name: its workload entry names a configuration, the
configuration's ``deployment_kind`` names ``bench/kinds/<kind>.py``, and that
module's ``Deployment`` class is what the harness drives.  A kind holds
everything that depends on what a tenant hands the service: the plan, the
data, the tenants' registration, each request, and the check against the
plain reference.  The harness holds the rest: devices, the compile clock,
the ledger, the server's life, the closed loop, the window and the readers.

The protocol, in the order the harness calls it:

``Deployment(cell, seed, devices)``
    Set-up on the host, inside ``setup_s``: the plan and every tenant's
    inputs, made from ``seed`` and ``cell.config`` / ``cell.mix``.
    ``devices`` are the cell's own (``cell.chips`` of them), for a kind that
    builds a mesh.  The instance has ``tenants``, the tenant names in client
    order, and ``server_kwargs``, extra keyword arguments of
    ``ReleaseServer(...)``.
``register(srv)``
    Register every tenant with the started server, with whatever keyword
    arguments and per-tenant state the kind needs (records placed on a mesh,
    say).  Inside ``setup_s``, before the warm batch.
``next_request(t) -> (release, request, item)``
    Tenant ``t``'s next ``ReleaseRequest``, its release index (which orders
    the check's sample and names a failure), and an opaque check item that
    the window's reservoir sample keeps beside the result.  Thread-safe per
    tenant: a tenant's clients call it concurrently.
``layer_inputs(srv) -> dict``
    The keyword arguments ``plan_groups`` and ``chain_fused`` of
    ``layers.Context``, after the window, in a traced run only.
``drop()``
    Let go of what the kind holds of the program's state, so the check runs
    with it freed.
``check(sample, journal, submitted) -> dict``
    Every number ``check.judge`` reads but ``failed``: ``sample`` is the
    window's sampled ``(item, result)`` pairs, ``journal`` the ledger's file,
    ``submitted`` the requests submitted per tenant.
"""

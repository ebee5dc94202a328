"""Arrival processes, one module each, found by the ``arrival`` key of a
traffic mix.  A module defines ``CLOSED`` (a closed backlog: every freed
slot is refilled at once, so requests have no due time) and
``gaps(mix, n)``: the ``n`` inter-arrival gaps, in seconds, of an open
loop at ``mix["rate_rps"]``, or ``None`` for a closed backlog.

The gaps are the distribution's ``n`` mid-quantiles, not random draws:
every seed offers the same gaps, in an order that the seed shuffles, so
two seeds differ in the order of the work and never in its amount.
"""

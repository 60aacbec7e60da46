"""Share of the traced window in which the device ran nothing, in %."""
from devtrace import idle_share_pct as read  # noqa: F401

"""Built-in passes. Importing this package registers them with
`core.REGISTRY`; a new pass is one module with a `@register_pass` class
(see README "Static analysis" for the recipe).
"""
from . import trace_hazard    # noqa: F401
from . import host_sync       # noqa: F401
from . import falsy_guard     # noqa: F401
from . import lock_order      # noqa: F401
from . import raw_lock        # noqa: F401
from . import swallowed_exception  # noqa: F401
from . import obs_schema      # noqa: F401

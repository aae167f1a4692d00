"""Plain reference implementations, one module per architecture family.

A configuration names its reference by module (``"reference"`` key); the
module imports nothing of the program under test."""

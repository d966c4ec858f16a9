"""Plain references, one file each, found by the configuration's ``"reference"`` key."""

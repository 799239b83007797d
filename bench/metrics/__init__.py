"""One reader per metric, found by the metric's name up to its first
``.``: ``read(run, name)`` returns the number, or None where the run
holds nothing to read (the harness then leaves the metric out)."""

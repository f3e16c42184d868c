"""One reader per metric stem: ``read(ctx)`` returns the number, or None
where the run has nothing to read it from (the metric is then left out)."""

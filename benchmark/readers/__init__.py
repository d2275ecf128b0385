"""One file per kind of source a metric can be read from.  A metric's file
(``end_to_end/<name>.json`` or ``layer_metrics/<name>.json``) names its
``reader``; the harness imports ``readers/<reader>.py`` and calls
``read(spec, run)``.  ``run`` is what one run observed (run.py ``Observed``).
A reader that finds nothing to read returns None and the harness leaves
the metric out of the line."""

"""On-chip benchmark of the serving path: one cell per configuration and traffic mix."""

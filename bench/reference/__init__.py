"""Plain references of the configurations, one module per architecture."""
